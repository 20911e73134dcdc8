"""model step layer: mean device time of one execution of the engine's
fused decode step (`_step_impl`), from the trace's executable line."""
from benchmark.lib.readers import executions


def read(view):
    count, seconds = executions(view, "step_impl")
    return 1e3 * seconds / count if count > 0 else None

"""engine layer: wall time per decode step that the device did not cover
— (traced window - device busy) / decode steps executed in it."""
from benchmark.lib.readers import executions


def read(view):
    steps, _ = executions(view, "step_impl")
    t = view["trace"]
    return 1e3 * (t["window_s"] - t["busy_s"]) / steps if steps > 0 else None

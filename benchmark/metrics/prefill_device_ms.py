"""engine layer: mean device time per admitted request of the engine's
prefill executables (`_prefill_impl`; `_chunk_impl` and `_reuse_impl` on a
prefix hit), from the trace's executable line."""
from benchmark.lib.readers import executions


def read(view):
    count, _ = executions(view, "prefill_impl", "chunk_impl")
    _, seconds = executions(view, "prefill_impl", "chunk_impl", "reuse_impl")
    return 1e3 * seconds / count if count > 0 else None

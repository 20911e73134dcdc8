"""admission layer: 95th percentile of the time a request spent before
its prefill began — first token seen minus due time, less its own prefill
as the engine's admission prices it (prompt tokens x the engine's
`prefill_ms_per_token` estimate; the engine has no span at prefill start
yet, see PERF.md Open questions)."""
from benchmark.lib.stats import percentile


def read(view):
    return percentile(view["counters"].get("queue_wait_ms") or [], 0.95)

"""harness: 95th percentile of how late the generator sent a request
after it was due. A starved generator must not read as a fast server."""
from benchmark.lib.stats import percentile


def read(view):
    return percentile(view["counters"].get("gen_late_ms") or [], 0.95)

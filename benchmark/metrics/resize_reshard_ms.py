"""live resize layer: `reshard_s` of the trainer's `resize_timing`,
median over the window's live resizes."""
from benchmark.lib.stats import median


def read(view):
    recs = view["counters"].get("resize_records") or []
    return median([1e3 * r["reshard_s"] for r in recs
                   if r.get("reshard_s") is not None])

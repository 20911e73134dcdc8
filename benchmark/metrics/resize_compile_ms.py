"""live resize layer: `compile_s` of the trainer's `resize_timing` (the
first dispatch after a resize: a prewarmed executable loaded, or a
compile), median over the window's live resizes."""
from benchmark.lib.stats import median


def read(view):
    recs = view["counters"].get("resize_records") or []
    return median([1e3 * r["compile_s"] for r in recs
                   if r.get("compile_s") is not None])

"""live resize layer: the pause of a live resize as the harness's own
stopwatch takes it (`save()` returned, no step in flight -> the first
step's result on the new mesh), median over the window's shrinks AND grows
(host clock). What `resize_pause_ms` was while it stood end to end: one
median over two modes, which no bound the contract allows held (PERF.md §2)."""
from benchmark.lib.stats import median


def read(view):
    return median(view["counters"].get("resize_pause_ms") or [])

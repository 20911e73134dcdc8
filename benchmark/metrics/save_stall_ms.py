"""checkpoint layer: how long `trainer.save()` blocked training, median
over the window's saves (host clock around the call)."""
from benchmark.lib.stats import median


def read(view):
    return median(view["counters"].get("save_stall_ms") or [])

"""live resize layer: span `resize.first_result` (the wait for the first step's
result on the new mesh), median over the window's grows."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "grow", "first_run")

"""live resize layer: span `resize.prewarm_load` (reading and deserializing the
prewarmed step executable), median over the window's shrinks."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "shrink", "step_load")

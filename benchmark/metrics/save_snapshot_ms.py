"""checkpoint layer: span `save.snapshot` (the state copied from the device into
the save engine's host buffers), median over the window's saves."""
from benchmark.lib import progspans


def read(view):
    return progspans.save_ms(view, "save.snapshot")

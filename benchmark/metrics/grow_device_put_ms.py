"""live resize layer: span `resize.device_put` (the reshard itself: `device_put`
of the state onto the new mesh, to ready), median over the window's grows."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "grow", "device_put")

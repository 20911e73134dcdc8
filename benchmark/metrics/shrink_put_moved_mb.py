"""live resize layer: tag `bytes_moved` of `resize.device_put` (bytes that had to
land on a device that did not hold that index of that leaf before), median over
the window's shrinks, in MB."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.put_moved_mb(view, "shrink")

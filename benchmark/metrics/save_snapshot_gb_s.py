"""checkpoint layer: tag `bytes` of `save.snapshot` (host bytes kept) over the
span's duration, median over the window's saves, in GB/s."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.snapshot_gb_s(view)

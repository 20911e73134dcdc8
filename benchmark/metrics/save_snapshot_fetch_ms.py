"""checkpoint layer: tag `fetch_s` of `save.snapshot` (time inside the `np.asarray`
of leaves and shards: waiting for the device's copy and reading it), median over
the window's saves, in ms."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.snapshot_tag_ms(view, "fetch_s")

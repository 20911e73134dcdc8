"""checkpoint layer: tag `copy_s` of `save.snapshot` (time inside the copies into
the save engine's pooled host buffers), median over the window's saves, in ms."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.snapshot_tag_ms(view, "copy_s")

"""live resize layer: of the time inside the window's `resize.device_put` spans,
the share during which a `save.persist` span of the ring was open: how much of
the reshard ran beside the writer threads of the save before it."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.put_beside_persist_pct(view)

"""checkpoint layer: span `save.snapshot.start_transfers` (a device-to-host copy
asked of every addressable shard of every leaf, before any is read), median over
the window's saves."""
from benchmark.lib import progspans


def read(view):
    return progspans.save_ms(view, "save.snapshot.start_transfers")

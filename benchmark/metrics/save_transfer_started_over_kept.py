"""checkpoint layer: tags `transfer_bytes_started` over `bytes` of `save.snapshot`,
each summed over the window's saves: what the snapshots asked the device to send
for each byte they kept (1 where only the replica that is read is asked for)."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.transfer_started_over_kept(view)

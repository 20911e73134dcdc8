"""live resize layer: span `resize.device_put.wait` (`block_until_ready` on the
resharded state: the transfers themselves), median over the window's grows."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.put_stage_ms(view, "grow", "wait")

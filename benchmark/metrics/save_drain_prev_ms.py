"""checkpoint layer: span `save.drain_prev` (`trainer.save()` waiting for the
previous asynchronous save to commit), median over the window's saves."""
from benchmark.lib import progspans


def read(view):
    return progspans.save_ms(view, "save.drain_prev")

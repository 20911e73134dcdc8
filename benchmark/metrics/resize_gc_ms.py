"""live resize layer: tags `gc_ms` of `resize.live` and `resize.first_step` (the
garbage collector's time while each was open), summed per resize, median over all
the window's resizes; 0 where no collection ran."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.resize_gc_ms(view)

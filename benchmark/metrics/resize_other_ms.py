"""live resize layer: the pause less the stages the other readers name (so:
drain, mesh, build_step, the rest of the first dispatch and whatever no span
covers), median over all the window's resizes. Large: the spans miss something."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_other_ms(view)

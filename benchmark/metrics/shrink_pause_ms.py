"""live resize layer: a SHRINK's whole pause, from the program's spans: start of
`resize.live` to end of `resize.first_step`, median over the window's shrinks."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "shrink", "pause")

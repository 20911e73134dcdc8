"""live resize layer: share of the window's resizes whose root tag `drain` reads
`deferred`: a save's write was in flight and was left running."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.root_tag_pct(view, "drain", "deferred")

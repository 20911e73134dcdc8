"""live resize layer: share of the window's resizes whose root tag `step_source`
reads `memory`: the new world's step was an executable the process held."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.root_tag_pct(view, "step_source", "memory")

"""Rows one pass into or out of the experts' order visits over the rows
the held experts served, both summed over layers and steps: 1 plus the
padding of each held expert's rows to whole tiles where a pass walks the
tiles in use, the layout's static worst case over the served rows where
it walks them all."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    moved, served = counters.get("rows_moved"), counters.get("rows_held")
    if not moved or not served or not sum(served):
        return None
    return float(sum(moved)) / float(sum(served))

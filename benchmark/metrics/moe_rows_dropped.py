"""Rows routed to a held expert that no expert computed, summed over
layers and steps: the dropless layer must read 0."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    dropped = model_counters().get("rows_dropped")
    return float(sum(dropped)) if dropped else None

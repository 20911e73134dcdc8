"""Query rows a step, summed over the layers, that kept another number of keys than min(position + 1, top-k): exact ties at the threshold only, so a handful, not thousands (`rows_off_count`, counted on the device)."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    off, steps = counters.get("rows_off_count"), counters.get("steps")
    if off is None or not steps or not steps[0]:
        return None
    return sum(off) / steps[0]

"""The largest |S| of the SSD scan's recurrent state at a chunk's end, over the Mamba-2 layers and the steps (`ssd_state_absmax`, a running maximum kept on the device): a state that grows says the decay is not what ran."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    tops = model_counters().get("ssd_state_absmax")
    return float(max(tops)) if tops else None

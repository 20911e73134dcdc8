"""The largest |S| of the gated delta rule's recurrent state at a chunk's end, over the linear-attention layers and the steps (`gdn_state_absmax`, a running maximum kept on the device): a state that grows says the decay or the delta correction is not what ran."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    tops = model_counters().get("gdn_state_absmax")
    return float(max(tops)) if tops else None

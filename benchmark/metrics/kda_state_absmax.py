"""The largest |S| of Kimi Delta Attention's recurrent state at a chunk's end, over the KDA layers and the steps (`kda_state_absmax`, a running maximum kept on the device): a state that grows says the decay is not what ran."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    tops = model_counters().get("kda_state_absmax")
    return float(max(tops)) if tops else None

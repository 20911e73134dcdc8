"""Share of the causal (query, key) pairs the learned selection kept, over all layers and steps, as the attention forward kernel counted them on the device (`pairs_kept`): top-k over the sequence length decides it (23.4 at 2048 of 16384), or the mask is not what the step ran."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    kept, steps = counters.get("pairs_kept"), counters.get("steps")
    if not kept or not steps or not steps[0]:
        return None
    t = view["traffic"]["seq_len"]
    rows = view["traffic"]["batch_per_chip"] * view["cell"]["chips"]
    return 100.0 * sum(kept) / (steps[0] * len(kept) * rows
                                * t * (t + 1) / 2.0)

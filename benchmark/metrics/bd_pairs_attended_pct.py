"""(query, key) pairs the attention forward counted under the mask it applied (`pairs_attended`, one head, counted on the device), over layers x steps x rows x (T^2 + T x block length): reads 100, or the two-stream block mask is not what the step ran."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    pairs, steps = counters.get("pairs_attended"), counters.get("steps")
    block = view["config"].get("block_length")
    if not pairs or not steps or not steps[0] or not block:
        return None
    t = view["traffic"]["seq_len"]
    rows = view["traffic"]["batch_per_chip"] * view["cell"]["chips"]
    return 100.0 * sum(pairs) / (steps[0] * len(pairs) * rows
                                 * (float(t) * t + float(t) * block))

"""The sum of a token's routing weights over its k chosen experts, held here or not, averaged over tokens, expert layers and steps (`route_weight_sum` over tokens x expert layers x steps): reads the configuration's `routed_scaling_factor` (2.448), or the normalisation over the chosen or the factor is missing."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    total, steps = counters.get("route_weight_sum"), counters.get("steps")
    if not total or not steps or not steps[0]:
        return None
    job = view["traffic"]
    tokens = job["batch_per_chip"] * view["cell"]["chips"] * job["seq_len"]
    layers = sum(1 for x in total if x > 0)     # a dense layer counts 0
    if not layers:
        return None
    return sum(total) / (tokens * layers * steps[0])

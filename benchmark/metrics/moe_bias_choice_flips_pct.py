"""100 x the (token, slot) choices of a sigmoid router that its selection bias changed — experts among the k largest of score + bias that the k largest of the score alone would not have held (`route_bias_flips`) — over all the choices of the expert layers (tokens x k x expert layers x steps): reads above 0, or the bias is not in the choice."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    flips, total = counters.get("route_bias_flips"), counters.get(
        "route_weight_sum")
    steps = counters.get("steps")
    if not flips or not total or not steps or not steps[0]:
        return None
    cfg, job = view["config"], view["traffic"]
    tokens = job["batch_per_chip"] * view["cell"]["chips"] * job["seq_len"]
    layers = sum(1 for x in total if x > 0)     # a dense layer counts 0
    if not layers:
        return None
    return 100.0 * sum(flips) / (tokens * cfg["num_experts_per_tok"]
                                 * layers * steps[0])

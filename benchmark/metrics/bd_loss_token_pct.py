"""Share of the clean positions that were masked and so carried loss (`loss_tokens`, counted on the device), over steps x rows x T: about 50 with one t ~ U[1e-3, 1] a block, and what the head's cost follows."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    counters = model_counters()
    tokens, steps = counters.get("loss_tokens"), counters.get("steps")
    if not tokens or not steps or not steps[0]:
        return None
    rows = view["traffic"]["batch_per_chip"] * view["cell"]["chips"]
    return 100.0 * tokens[0] / (steps[0] * rows * view["traffic"]["seq_len"])

"""Share of its roofline reached by the `kda_fwd` Pallas kernel's calls (Kimi Delta Attention's chunked delta rule at a vector decay, forward: the state carried chunk after chunk, its rows decayed each at its rate): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes, the same whatever implements the sequential part) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "kda_fwd")

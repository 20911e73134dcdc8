"""Share of its roofline reached by the `kda_bwd` Pallas kernel's calls (Kimi Delta Attention's chunked delta rule at a vector decay, backward: the chunks from the last to the first, the state's cotangent carried): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes, the same whatever implements the sequential part) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "kda_bwd")

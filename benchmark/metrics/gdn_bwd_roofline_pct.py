"""Share of its roofline reached by the `gdn_bwd` Pallas kernel's calls (the gated delta rule's chunked scan, backward: the state's cotangent carried from the last chunk to the first): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "gdn_bwd")

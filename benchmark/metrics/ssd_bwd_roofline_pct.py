"""Share of its roofline reached by the `ssd_bwd` Pallas kernel's calls (Mamba-2's chunked SSD scan, backward: the chunks from the last to the first, the state's cotangent carried): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes, the same whatever implements the scan) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "ssd_bwd")

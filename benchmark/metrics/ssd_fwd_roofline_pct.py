"""Share of its roofline reached by the `ssd_fwd` Pallas kernel's calls (Mamba-2's chunked SSD scan, forward: state carried chunk after chunk): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes, the same whatever implements the scan) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "ssd_fwd")

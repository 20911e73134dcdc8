"""Share of its roofline reached by the `flash_fwd_stream` Pallas kernel's calls (causal attention forward with k and v streamed through the grid, where a key-value head's k + v pass the resident limit): the larger of their REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs, for the calls one step makes) over their measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "flash_fwd_stream")

"""Share of its roofline the `moe_tgmm` Pallas kernel reaches: the larger of
its compute and memory time at the chip's peaks over its measured time
(operations and bytes: benchmark/program/<family>.py:kernel_costs)."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "moe_tgmm")

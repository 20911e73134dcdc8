"""Share of its roofline the `dsa_index_tau` Pallas kernel (thresholds of the learned selection (the exact k-th largest index score of every query)) reaches: the larger of its REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs: nothing counted for a pair the selection drops) over its measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "dsa_index_tau")

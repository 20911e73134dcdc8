"""Share of its roofline the `dsa_bwd` Pallas kernel (backward of attention over the selected keys and of the indexer) reaches: the larger of its REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs: nothing counted for a pair the selection drops) over its measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "dsa_bwd")

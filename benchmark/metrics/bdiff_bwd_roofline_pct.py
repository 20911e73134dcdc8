"""Share of its roofline the `bdiff_bwd` Pallas kernel (attention backward under the two-stream block mask) reaches: the larger of its REQUIRED compute and memory time at the chip's peaks (benchmark/program/<family>.py:kernel_costs: nothing counted for a pair outside the mask, nor for the last layer's clean queries) over its measured time."""
from benchmark.lib.kernel_readers import kernel_roofline_pct


def read(view):
    return kernel_roofline_pct(view, "bdiff_bwd")

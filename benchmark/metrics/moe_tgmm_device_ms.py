"""Device time of the `moe_tgmm` Pallas kernel's calls, per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "moe_tgmm")

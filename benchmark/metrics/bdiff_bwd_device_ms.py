"""Device time of the `bdiff_bwd` Pallas kernel's calls (attention backward under the two-stream block mask), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "bdiff_bwd")

"""Device time of the `bdiff_fwd` Pallas kernel's calls (attention forward under the two-stream block mask), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "bdiff_fwd")

"""Device time of the `flash_fwd_resident` Pallas kernel's calls, per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "flash_fwd_resident")

"""Device time of the attention backward's Pallas kernels (`flash_bwd`, `flash_bwd_dq`, `flash_bwd_dkv`), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "flash_bwd")

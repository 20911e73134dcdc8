"""Device time of the `flash_fwd_stream` Pallas kernel's calls (causal attention forward with k and v streamed through the grid, where a key-value head's k + v pass the resident limit), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "flash_fwd_stream")

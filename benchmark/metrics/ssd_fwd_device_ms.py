"""Device time of the `ssd_fwd` Pallas kernel's calls (Mamba-2's chunked SSD scan, forward: state carried chunk after chunk), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "ssd_fwd")

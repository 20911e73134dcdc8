"""Device time of the `ssd_bwd` Pallas kernel's calls (Mamba-2's chunked SSD scan, backward: the chunks from the last to the first, the state's cotangent carried), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "ssd_bwd")

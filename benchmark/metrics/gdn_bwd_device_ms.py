"""Device time of the `gdn_bwd` Pallas kernel's calls (the gated delta rule's chunked scan, backward: the state's cotangent carried from the last chunk to the first), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "gdn_bwd")

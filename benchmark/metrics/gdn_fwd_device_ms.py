"""Device time of the `gdn_fwd` Pallas kernel's calls (the gated delta rule's chunked scan, forward: state carried chunk after chunk), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "gdn_fwd")

"""Device time of the `dsa_fwd` Pallas kernel's calls (attention forward over the selected keys), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "dsa_fwd")

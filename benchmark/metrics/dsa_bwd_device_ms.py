"""Device time of the `dsa_bwd` Pallas kernel's calls (backward of attention over the selected keys and of the indexer), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "dsa_bwd")

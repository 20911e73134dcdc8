"""Device time of the `dsa_index_kl` Pallas kernel's calls (indexer's KL term), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "dsa_index_kl")

"""Device time of the `dsa_index_tau` Pallas kernel's calls (thresholds of the learned selection (the exact k-th largest index score of every query)), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "dsa_index_tau")

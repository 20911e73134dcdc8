"""Device time of the `kda_fwd` Pallas kernel's calls (Kimi Delta Attention's chunked delta rule at a vector decay, forward: the state carried chunk after chunk, its rows decayed each at its rate), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "kda_fwd")

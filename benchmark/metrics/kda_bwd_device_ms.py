"""Device time of the `kda_bwd` Pallas kernel's calls (Kimi Delta Attention's chunked delta rule at a vector decay, backward: the chunks from the last to the first, the state's cotangent carried), per step and chip."""
from benchmark.lib.kernel_readers import kernel_device_ms


def read(view):
    return kernel_device_ms(view, "kda_bwd")

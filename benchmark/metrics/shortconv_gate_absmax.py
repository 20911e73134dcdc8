"""The largest |C * z| a gated short convolution formed, float32, before it is rounded for the out-projection, over the conv layers and the steps (`conv_gate_absmax`, a running maximum kept on the device): the product is CUBIC in the layer's normed input, and this says it stays inside bfloat16's range."""
from benchmark.lib.kernel_readers import model_counters


def read(view):
    tops = model_counters().get("conv_gate_absmax")
    return float(max(tops)) if tops else None

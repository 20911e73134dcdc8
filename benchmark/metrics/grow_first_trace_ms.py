"""live resize layer: `jax_trace_s + jax_lower_s` of span `resize.first_dispatch`
(JAX tracing and lowering the step again at the first call after a grow)."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "grow", "first_trace")

"""live resize layer: `jax_compile_s + jax_cache_load_s` of span
`resize.first_dispatch` (the executable compiled, or loaded from the
persistent cache, at the first call after a grow)."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "grow", "first_load")

"""model parts: device self time under the `attn.*` scopes, their Pallas kernels included, per step and chip."""
from benchmark.lib.scope_readers import part_ms


def read(view):
    return part_ms(view, "attn")

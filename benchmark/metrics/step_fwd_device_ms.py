"""model parts: device self time of the forward pass, per step and chip."""
from benchmark.lib.scope_readers import phase_ms


def read(view):
    return phase_ms(view, "fwd")

"""live resize layer: span `resize.device_put.dispatch` (the call of
`jax.device_put` on the state: per-leaf work of the interpreter and the runtime),
median over the window's shrinks."""
from benchmark.lib import stagespans


def read(view):
    return stagespans.put_stage_ms(view, "shrink", "dispatch")

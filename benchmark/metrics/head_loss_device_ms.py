"""model parts: device self time of the head and the loss (`lm_head`, `loss.*`, `loop.exit_gate`), per step and chip."""
from benchmark.lib.scope_readers import part_ms


def read(view):
    return part_ms(view, "head_loss")

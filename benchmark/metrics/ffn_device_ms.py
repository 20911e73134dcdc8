"""model parts: device self time under `moe.*` and `ffn.dense`, the grouped products included, per step and chip."""
from benchmark.lib.scope_readers import part_ms


def read(view):
    return part_ms(view, "ffn")

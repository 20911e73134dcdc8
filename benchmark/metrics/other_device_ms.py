"""model parts: device self time of everything else: `embed`, `norm`, a loop's own operations, and what carries no scope."""
from benchmark.lib.scope_readers import part_ms


def read(view):
    return part_ms(view, "other")

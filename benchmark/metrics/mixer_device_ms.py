"""model parts: device self time under `mixer.gdn.*` and `ssm.*`, their Pallas kernels included, per step and chip."""
from benchmark.lib.scope_readers import part_ms


def read(view):
    return part_ms(view, "mixer")

"""live resize layer: span `resize.prewarm_fingerprint` (a trace and lowering of
the step that only names the prewarmed artifact), median over the shrinks."""
from benchmark.lib import progspans


def read(view):
    return progspans.resize_ms(view, "shrink", "fingerprint")

"""model parts: device self time a step and chip under the scope `mixer.conv.gate` — everything between a gated short convolution's two products (B * x, the taps' sums, C * z and their cotangents), in every phase (forward, remat, backward), whatever XLA makes of it. None, with the reason on stderr, where the run's trace holds no operation under that scope (a program without the mixer, or one whose compiler fused all of it into the neighbouring products)."""
from benchmark.lib import harness
from benchmark.lib.scope_readers import table

SCOPE = "mixer.conv.gate"


def read(view):
    ms = table(view)
    if ms is None:
        return None
    hits = [v for (scope, _), v in ms.items() if scope == SCOPE]
    if not hits:
        harness.log("%s: no operation of the traced steps lies under the "
                    "scope %s" % (__name__, SCOPE))
        return None
    return sum(hits)

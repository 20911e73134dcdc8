"""live resize layer: tag `arrays_crossed` of `resize.device_put` (arrays the
reshard handed to the runtime to copy across devices: one a leaf a chip gained,
or a handful where the state crosses packed), median over the window's grows.
A program without the tag gives nothing to read."""
from benchmark.lib import stagespans
from benchmark.lib.stats import median


def read(view):
    got = [stagespans._tag(s, "arrays_crossed") for s in stagespans._spans(
        stagespans._resizes(view, "grow"), "resize.device_put")]
    return median([v for v in got if v is not None])

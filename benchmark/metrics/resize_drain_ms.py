"""live resize layer: span `resize.drain` (the resize waiting for the save in
flight to commit, where its reshard reads the committed version; short where it
does not), median over all the window's resizes, shrinks and grows alike."""
from benchmark.lib import progspans
from benchmark.lib.stats import median


def read(view):
    got = [progspans._ms(trace, "resize.drain")
           for _, trace in progspans._traces(view, "resize.live")]
    return median([v for v in got if v is not None])

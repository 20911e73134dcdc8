"""Order statistics used by every metric (one definition, kept here)."""


def percentile(values, q):
    """Nearest-rank percentile, q in [0, 1]; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals) - 1, int(q * len(vals)))]


def median(values):
    vals = sorted(values)
    if not vals:
        return None
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])

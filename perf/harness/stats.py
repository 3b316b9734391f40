"""Order statistics used by every metric (no numpy: a percentile of twenty
requests must not depend on an array library's default)."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between the
    order statistics at rank ``q/100 * (n - 1)`` (numpy's default)."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    rank = (len(vals) - 1) * float(q) / 100.0
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)


def median(values):
    return percentile(values, 50)


def mean(values):
    vals = [float(v) for v in values]
    return sum(vals) / len(vals) if vals else None

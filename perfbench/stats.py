"""Order statistics used by every metric (kept with the benchmark)."""

import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default rule), on plain Python floats."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (float(q) / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values):
    xs = [float(v) for v in values]
    if not xs:
        raise ValueError("mean of no values")
    return sum(xs) / len(xs)

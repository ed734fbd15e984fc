"""The arithmetic of the end-to-end metrics, kept where no later PR can
change it: percentiles of ALL requests of a window (no chunking, no
trimming) and rates over ALL the window's seconds.  ``selfcheck.py`` holds a
made-up window with a stall against these functions.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default rule), of every value given."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def rate(total: float, window_s: float) -> float:
    """Work completed inside the window over the whole window's seconds."""
    if window_s <= 0:
        raise ValueError("empty window")
    return total / window_s


def spread(values) -> float:
    """Inter-quartile distance as a share of the median (the driver's rule:
    ``statistics.quantiles(values, n=4)``)."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

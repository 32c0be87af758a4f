"""The definitions every metric is reduced with."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile: the smallest value with at least
    ``q`` percent of the values at or below it.  A missing value (a failed
    request) is ``inf`` and sorts last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def rate(count: float, seconds: float) -> float:
    """Work per second over the whole window, idle time included."""
    if seconds <= 0:
        raise ValueError("a rate needs a window")
    return count / seconds

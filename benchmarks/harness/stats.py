"""Percentiles and failure accounting, over ALL requests of a window."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), over every value given."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latencies_with_misses(ok_ms: list[float], n_failed: int,
                          timeout_ms: float) -> list[float]:
    """A failed or refused request stays in the percentile as a miss: the
    timeout or the longest latency seen, whichever is larger."""
    miss = max([timeout_ms] + ok_ms)
    return list(ok_ms) + [miss] * n_failed


def spread(values: list[float]) -> float:
    """Inter-quartile distance as a share of the median, by
    ``statistics.quantiles(values, n=4)`` as the contract measures it."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

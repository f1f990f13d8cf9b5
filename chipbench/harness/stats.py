"""Percentiles and spreads, in one place so every metric uses the same
arithmetic."""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(xs: Sequence[float], p: float) -> Optional[float]:
    """``p``-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default ``linear`` method).  ``None`` for an
    empty sample: a missing tail is absent, never 0."""
    if not xs:
        return None
    s = sorted(float(x) for x in xs)
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile wants 0 <= p <= 100, got {p}")
    r = (len(s) - 1) * p / 100.0
    lo = math.floor(r)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (r - lo)


def beyond(n: int, p: float) -> int:
    """How many of ``n`` samples lie beyond the ``p``-th percentile."""
    return n - math.ceil(n * p / 100.0)


def iqr_share(values: Sequence[float]) -> float:
    """Spread of a set of runs: the distance between the first and third
    quartile (``statistics.quantiles(values, n=4)``) over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

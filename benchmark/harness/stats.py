"""Percentile, rate and spread arithmetic, kept with the benchmark so every
PR computes the same number the same way."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in (0, 100]): the smallest value with at
    least q % of the samples at or below it. None for no samples."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def rate(count: float, seconds: float) -> float:
    """Work over ALL the time of the window, stalls included."""
    if seconds <= 0:
        raise ValueError("a rate needs a window of positive length")
    return count / seconds


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

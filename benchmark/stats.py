"""Arithmetic the benchmark owns: exact percentiles over raw samples,
the quartile spread the bounds are set from, rates over a window."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], pct: float) -> Optional[float]:
    """Exact percentile by linear interpolation between order
    statistics (the 'inclusive' definition: pct 0 is the minimum,
    100 the maximum). None for no samples."""
    if not values:
        return None
    ordered = sorted(values)
    if len(ordered) == 1:
        return float(ordered[0])
    rank = (pct / 100.0) * (len(ordered) - 1)
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    frac = rank - low
    return float(ordered[low] + (ordered[high] - ordered[low]) * frac)


def tail_percentile(values: Sequence[Optional[float]], pct: float,
                    failed_value: float = math.inf) -> Optional[float]:
    """Percentile over ALL requests: a request with no sample (it
    failed, was shed, or never finished) counts as missing any limit,
    i.e. as +inf, so enough failures push the tail to infinity instead
    of quietly shrinking the population."""
    if not values:
        return None
    filled = [failed_value if v is None else v for v in values]
    return percentile(filled, pct)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with statistics.quantiles(n=4) (the driver's definition)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def rate_in_window(times: Sequence[float], start: float,
                   end: float) -> float:
    """Events per second that fell inside [start, end)."""
    if end <= start:
        raise ValueError("empty window")
    return sum(1 for t in times if start <= t < end) / (end - start)

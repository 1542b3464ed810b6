"""Arithmetic on samples: percentiles, medians and the spread the bounds
are set from. Plain Python, so that no library's default can move it."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (numpy's default method). Raises on no
    samples: a tail of nothing is not 0."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with `statistics.quantiles(values, n=4)` as the driver takes
    them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / median(values)


def union_length(intervals: Sequence[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    covered, reach = 0.0, -math.inf
    for start, end in sorted(intervals):
        if end <= reach:
            continue
        covered += end - max(start, reach)
        reach = end
    return covered

"""The benchmark's arithmetic on host-clock samples.

Kept apart from the program so that every run computes a percentile, a
rate and a spread the same way.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample such that at least
    ``q`` percent of the samples are at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"percentile rank {q} outside (0, 100]")
    xs = sorted(values)
    rank = math.ceil(q / 100.0 * len(xs))
    return float(xs[max(rank, 1) - 1])


def rate(count: int, window_s: float) -> float:
    """All the work over all the time of the window."""
    if window_s <= 0.0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartiles, as a share of the
    median (``statistics.quantiles`` with n=4, its default method)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

"""Exact order statistics over raw samples (no buckets, no interpolation
between buckets)."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    return float(xs[max(math.ceil(q / 100.0 * len(xs)), 1) - 1])


def median(values) -> float:
    return float(statistics.median(values))


def spread(values) -> float:
    """Distance between the first and third quartile over the median,
    as ``statistics.quantiles(values, n=4)`` gives the quartiles."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

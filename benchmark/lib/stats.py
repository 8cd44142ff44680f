"""The arithmetic from samples to a reported number. Kept here so that a
PR which claims a gain cannot change how its number is computed."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """Nearest-rank ``q``-th percentile (0 < q <= 100): the smallest sample
    with at least ``q`` percent of the samples at or below it. ``None`` for
    no samples."""
    return percentile_failed_last(values, 0, q, failed_value=math.nan)


def percentile_failed_last(values, n_failed: int, q: float,
                           failed_value: float) -> float | None:
    """Percentile over ``values`` plus ``n_failed`` requests that never
    answered. Those rank last (a request that never answered missed any
    limit); if the percentile lands on one, the result is ``failed_value``
    (the window plus the drain limit: the longest any request was waited
    for)."""
    n = len(values) + n_failed
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    if rank > len(values):
        return float(failed_value)
    return float(sorted(values)[rank - 1])


def median(values) -> float | None:
    """Median with the two middle samples averaged."""
    values = sorted(values)
    if not values:
        return None
    mid = len(values) // 2
    if len(values) % 2:
        return float(values[mid])
    return (values[mid - 1] + values[mid]) / 2.0


def quantile(values, p: float) -> float:
    """Linear-interpolation quantile of a non-empty sample, 0 <= p <= 1."""
    values = sorted(values)
    pos = p * (len(values) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def quartile_spread(values) -> float | None:
    """Distance between the quartiles over the median: the spread the
    driver reads between runs of one cell."""
    if len(values) < 2:
        return None
    med = median(values)
    return (quantile(values, 0.75) - quantile(values, 0.25)) / med if med else None

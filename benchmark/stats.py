"""Rate, percentile and spread arithmetic, kept with the benchmark so
every PR computes them the same way."""
from __future__ import annotations

import statistics
from typing import Optional, Sequence


def rate(count: float, seconds: float) -> Optional[float]:
    """All the work over all the time: ``count`` per second of wall."""
    return count / seconds if seconds > 0 and count > 0 else None


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile of every value, interpolated between the
    two nearest ranks (``statistics.quantiles``, inclusive method)."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    if p == 50:
        return float(statistics.median(xs))
    return float(statistics.quantiles(xs, n=100, method="inclusive")[
        int(p) - 1])


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile, as a share of the
    median (``statistics.quantiles(values, n=4)``, the driver's rule)."""
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None

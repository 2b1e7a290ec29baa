"""Percentiles and spreads, the way the contract defines them."""

from __future__ import annotations

import statistics


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank-with-interpolation percentile (numpy's default
    'linear'), q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as statistics.quantiles(values, n=4) gives them."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

"""Percentile and spread arithmetic of the benchmark (plain Python, no
numpy: the same numbers whatever the array library does)."""

from __future__ import annotations


def percentile(values, q: float) -> float:
    """``q`` in [0, 100]; linear interpolation between the two closest
    ranks of the sorted sample (rank = q/100 * (n-1))."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile rank {q} outside [0, 100]")
    rank = q / 100.0 * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile — a
    tail is only reported where at least ten do."""
    return int(n * (100.0 - q) / 100.0)


def spread(values) -> float:
    """Run-to-run spread as the driver reads it: the distance between the
    quartiles over the median."""
    med = median(values)
    if med == 0:
        raise ValueError("spread of a sample whose median is 0")
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(med)

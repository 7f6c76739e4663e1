"""Arithmetic on samples: medians, quartiles, tails, spread.

Pure Python and pure functions: the yardstick a later PR may not move.  A
timing is reported as a median and the highest percentile that has at least
ten samples beyond it (choosing-metrics §1), so a tail is only ever read
from a window that holds enough requests to carry it.
"""

from typing import Optional, Sequence

# the tails this benchmark will name, in rising order
PERCENTILES = (50.0, 90.0, 95.0, 99.0, 99.9)
BEYOND = 10  # samples that must lie beyond a percentile for it to be read


def quantile(samples: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks; ``q`` in [0, 1]."""
    if not samples:
        raise ValueError("quantile of no samples")
    ordered = sorted(samples)
    pos = q * (len(ordered) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> float:
    return quantile(samples, 0.5)


def quartiles(samples: Sequence[float]) -> tuple:
    return quantile(samples, 0.25), quantile(samples, 0.5), quantile(samples, 0.75)


def beyond(n: int, percentile: float) -> float:
    """How many of ``n`` samples lie beyond ``percentile`` (rounded at the
    ninth decimal: 100 - 99.9 is not exact in binary)."""
    return round(n * (100.0 - percentile) / 100.0, 9)


def highest_percentile(n: int) -> Optional[float]:
    """The highest of PERCENTILES with at least BEYOND of ``n`` samples
    beyond it; None when even the median has fewer."""
    best = None
    for p in PERCENTILES:
        if beyond(n, p) >= BEYOND:
            best = p
    return best


def tail(samples: Sequence[float], percentile: float) -> Optional[float]:
    """``percentile`` of the samples, or None where the window holds too few
    for ten to lie beyond it — a tail from fewer is one request's luck."""
    if beyond(len(samples), percentile) < BEYOND:
        return None
    return quantile(samples, percentile / 100.0)


def spread(samples: Sequence[float]) -> float:
    """The driver's measure of run-to-run noise: the distance between the
    quartiles over the median."""
    q1, q2, q3 = quartiles(samples)
    return (q3 - q1) / q2 if q2 else float("inf")

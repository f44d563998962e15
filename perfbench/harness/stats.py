"""Percentiles of latency samples, and which percentile a sample supports."""
import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), or None for no samples."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (100 - q) / 100.0 + 1e-9))


def highest_supported(n, candidates=(50, 90, 95, 99, 99.9), beyond=10):
    """The highest percentile of ``candidates`` that ``n`` samples support:
    at least ``beyond`` samples lie past it.  None when not even the
    lowest is supported (fewer than 20 samples for the median)."""
    ok = [q for q in candidates if samples_beyond(n, q) >= beyond]
    return max(ok) if ok else None

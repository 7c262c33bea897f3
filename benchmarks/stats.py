"""Order statistics the benchmark reports and the driver's spread rule."""

from __future__ import annotations

import statistics


def percentile(samples, p):
    """Linear-interpolation percentile (numpy's default method) of an
    unsorted, non-empty sequence; `p` in [0, 100]."""
    if not 0 <= p <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {p}")
    data = sorted(samples)
    if not data:
        raise ValueError("no samples")
    rank = (len(data) - 1) * (p / 100.0)
    lo = int(rank)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (rank - lo)


def median(samples):
    return percentile(samples, 50)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)`:
    the rule the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

"""The arithmetic behind the end-to-end metrics: copied in so that a
later PR cannot change what a number means (original:
ceph_tpu/common/perf_counters.py::percentiles_from_samples)."""

from __future__ import annotations

import math


def percentile_nearest_rank(samples, q: float) -> float:
    """The ceil(q*n)-th order statistic (1-indexed) of `samples`."""
    if not samples:
        raise ValueError("no samples")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    s = sorted(samples)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def rate_per_s(amount: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return amount / seconds

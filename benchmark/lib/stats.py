"""Metric arithmetic kept with the benchmark: percentiles, spreads and
the rule for requests that failed."""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list: the
    smallest value with at least q% of the sample at or below it. No
    interpolation, so a p95 is always a latency some request saw."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values):
    return statistics.median(values)


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles as ``statistics.quantiles(n=4)`` gives
    them: the spread the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def with_failures(latencies, n_failed, window_s):
    """A failed or refused request counts as the window's length."""
    return list(latencies) + [float(window_s)] * int(n_failed)

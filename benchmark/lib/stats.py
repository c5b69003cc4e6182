"""Metric arithmetic kept with the benchmark: percentiles, spreads and
the rule for requests that failed."""

from __future__ import annotations

import math
import re
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


def beyond(n, q):
    """How many of ``n`` samples lie beyond their nearest-rank q-th
    percentile; a tail wants ten (the ``choosing-metrics`` guide)."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


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


def without_farthest(values):
    """The set with the run farthest from its median left out: what the
    driver's check reads for tightness, so that one far-off run in a
    set does no harm and two do."""
    if len(values) < 3:
        return list(values)
    med = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - med))[:-1]


def range_share(values):
    """Largest minus smallest as a share of the median: ISSUE 35's
    stricter reading of a set, taken over ``without_farthest``."""
    return (max(values) - min(values)) / statistics.median(values)


LATENCY = re.compile(r"(ttft|tpot)_(?:p(\d+)|(mean))_ms")


def latency_statistic(name, latencies):
    """``ttft_p95_ms``, ``tpot_p50_ms``, ``ttft_mean_ms``...: the named
    statistic, in ms, of ``latencies[ttft|tpot]`` (seconds, one a
    request); a percentile is nearest-rank. None where the name is of
    another form or the list is missing or empty."""
    m = LATENCY.fullmatch(name)
    vals = (latencies or {}).get(m.group(1)) if m else None
    if not vals:
        return None
    if m.group(3):
        return 1e3 * sum(vals) / len(vals)
    return 1e3 * percentile(vals, int(m.group(2)))

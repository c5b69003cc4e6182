"""Server and scheduler: the median time to the first token, nearest
rank, over the runner's own list (see ``ttft_p95_ms``, the tail of the
same list). The end-to-end statistic since PR 35 is the mean of that
list, ``ttft_mean_ms``: of the ladder it is the one whose runs agree
closely enough for a bound (PERF.md, section 2); the median and the
tail are read here, without one, so that the ledger keeps both."""

from benchmark.lib import stats


def read(ctx):
    return stats.latency_statistic("ttft_p50_ms", ctx.get("latencies"))

"""Server and scheduler: the tail of the time to the first token,
nearest-rank p95 over the requests sent in the window of (the
``first_token`` mark) - (the time the request was due), a request with
no first token counting as the window's length: the runner's own list,
the one its end-to-end statistic (``ttft_mean_ms``) is taken from. It
was an end-to-end metric until PR 35: a run has some 200 requests, and
the ten beyond their p95 change places from run to run by more than any
bound may allow (PERF.md, section 2), so the tail is read here, without
a bound, beside the steadier statistic that is held to one."""

from benchmark.lib import stats


def read(ctx):
    return stats.latency_statistic("ttft_p95_ms", ctx.get("latencies"))

"""Load generator: how late requests were sent, sent minus due, on the
generator's own clock. A starved generator must not read as a fast
server."""

from benchmark.lib import stats


def read(ctx):
    lags = [1e3 * (r["sent"] - r["due"]) for r in ctx.get("records", [])]
    return stats.percentile(lags, 95) if lags else None

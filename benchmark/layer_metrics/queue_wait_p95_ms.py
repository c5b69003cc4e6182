"""Server and scheduler: time from ``queued`` to ``admitted`` on the
request's own trace, summed over its stints (a preempted request queues
again), 95th percentile over the requests admitted."""

from benchmark.lib import stats


def read(ctx):
    waits = [1e3 * r["queue_wait"] for r in ctx.get("records", [])
             if any(s == "admitted" for s, _ in r["events"])]
    return stats.percentile(waits, 95) if waits else None

"""Engine step: nearest-rank p95, over the requests finished in the
window, of the time between consecutive token stamps of one request on
its own ``RequestTrace``: ``first_token``, then each ``decode_chunk``
mark. The longest wait a streaming client sees between two deliveries:
a prefill admitted between two chunks lengthens it, and ``tpot``
averages it away. Needs the program's marks and the annotations' run
(``--trace 1``), no trace."""

from benchmark.lib import stats


def read(ctx):
    window = ctx.get("window")
    gaps = []
    for r in ctx.get("records") or []:
        retired = [t for s, t in r["events"] if s == "retired"]
        first = [t for s, t in r["events"] if s == "first_token"]
        if not (r["ok"] and first and retired) \
                or window and retired[-1] > window[1]:
            continue
        stamps = [first[0]] + [t for t, _ in r["chunks"]]
        gaps += [b - a for a, b in zip(stamps, stamps[1:])]
    return 1e3 * stats.percentile(gaps, 95) if gaps else None

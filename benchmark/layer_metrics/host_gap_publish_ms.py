"""Engine step: the part of ``host_gap_ms`` that lies under
``engine.publish``: the Python loop over the rows that stamps, charges
and retires."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    return None if spans is None else spans.gap_ms("publish")

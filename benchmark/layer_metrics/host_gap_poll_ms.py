"""Server and scheduler: the part of ``host_gap_ms`` that lies under
``engine.poll``: ``BatchingServer``'s drain of its queue, the timed
``get`` included."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    return None if spans is None else spans.gap_ms("poll")

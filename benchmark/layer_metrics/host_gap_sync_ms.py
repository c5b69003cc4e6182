"""Engine step: the part of ``host_gap_ms`` that lies under
``engine.host_sync``: the device is done and the tokens are not on the
host yet."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    return None if spans is None else spans.gap_ms("host_sync")

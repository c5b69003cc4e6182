"""Engine step: the part of ``host_gap_ms`` that lies under
``engine.prepare`` and ``engine.launch``: the decode prologue, the
uploads and the dispatch, until the device starts."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    return None if spans is None else spans.gap_ms("prepare", "launch")

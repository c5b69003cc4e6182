"""Device: of all the seconds of the traced span in which the chip ran
no operation (before prefills too), the share that lies under some
``engine.*`` phase of the serving thread (``benchmark/lib/host_spans.py``).
What is left is host work nobody named, or another thread's."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    share = None if spans is None else spans.attributed_share()
    return None if share is None else 100.0 * share

"""Engine step: mean, over the decode launches of the traced span whose
``engine.launch`` annotation and ``jit_decode_chunk_paged`` module both
lie in the trace, of the time the chip ran no operation between the end
of the paged program before it and its own start
(``benchmark/lib/host_spans.py``): what the host costs a decode chunk.
One-op programs in between count as busy."""

from benchmark.lib import host_spans


def read(ctx):
    spans = host_spans.of(ctx)
    return None if spans is None else spans.gap_ms()

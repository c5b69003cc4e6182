"""Kernels: the cold prefill's dense latent attention against its
roofline in the traced part of the window. Least time: the operations
``benchmark/kernels/mla_prefill.py`` counts (``n (n + 1) / 2`` causal
pairs a layer over a prompt's first ``n`` tokens, the expanded form's
count) for the blocks of each prefill launch whose events the trace
holds, over the chip's bfloat16 peak; over the device time of those
launches' events under ``mla_prefill_attn``
(``benchmark/lib/dsa_span.py``). A launch that began inside the trace
shows its first blocks, so its work is that of a prompt's first ``blocks
x 256`` tokens (the launch's own ``n`` from its entry where the trace
holds all of its blocks); the tail of a launch that began before the
trace shows its last blocks, counted from the entry of the last prefill
launched before the span, and left out, time and work, where there is
none. The absorbed form does 3.4 times the operations a pair and reads
below its share."""

from benchmark.kernels import mla_prefill
from benchmark.lib import dsa_span

SCOPE = "mla_prefill_attn"


def read(ctx):
    peaks, cfg = ctx.get("peaks"), ctx.get("cfg") or {}
    found = [s for s in dsa_span.segments(ctx) or [] if s.kind == "prefill"]
    if not peaks or not found:
        return None
    near = [e for _, e in dsa_span.launches_near_span(ctx, "prefill", 60.0)]
    lo = (ctx.get("trace_span") or (0, 0))[0]
    spent = causal = 0.0
    said = []
    for seg in found:
        n = seg.units * dsa_span.BLOCK_ROWS
        if seg.part == "tail":
            ahead = [e for e in near if e[0] < lo and e[2] >= seg.units]
            if not ahead:
                continue
            pairs = mla_prefill.pairs(ahead[-1][4]) \
                - mla_prefill.pairs(max(ahead[-1][4] - n, 0))
        else:
            entry = [e for e in near if e[2] == round(seg.units)]
            if entry and abs(seg.units - round(seg.units)) < 1e-6:
                n = min(e[4] for e in entry)    # never more than it had
            pairs = mla_prefill.pairs(n)
        spent += seg.seconds.get(SCOPE, 0.0)
        causal += pairs
        said.append(f"{seg.part}:{seg.units:.2f}")
    if not spent:
        return None
    least = mla_prefill.least_seconds(cfg["num_hidden_layers"] * causal, cfg,
                                      peaks)
    print(f"mla_prefill_roofline: seconds {spent:.6f} launches "
          f"{' '.join(said)} (part:blocks) causal_pairs_a_layer "
          f"{causal:.0f} least_s {least:.6f}")
    return 100.0 * least / spent

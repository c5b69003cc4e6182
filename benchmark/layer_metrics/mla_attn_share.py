"""Engine step: device time of the dense latent attention of both paged
programs (the events under the scopes ``mla_prefill_attn`` and
``mla_dense_decode``, ``benchmark/lib/dsa_span.py``) over the device's
busy time, in the traced part of the window: whether the cell's traffic
puts the work where the architecture differs."""

from benchmark.lib import dsa_span

SCOPES = ("mla_prefill_attn", "mla_dense_decode")


def read(ctx):
    trace = ctx.get("trace")
    found = dsa_span.segments(ctx)
    if not found or not trace.busy_s:
        return None
    by = {}
    for seg in found:
        for scope, s in seg.seconds.items():
            key = f"{seg.kind}.{scope}"
            by[key] = by.get(key, 0.0) + s
    spent = sum(seg.seconds.get(s, 0.0) for seg in found for s in SCOPES)
    if not spent:
        return None
    print("mla_attn_share: busy_s %.6f launches %s " % (
        trace.busy_s, " ".join(f"{s.kind}:{s.part}:{s.units:.2f}"
                               for s in found))
        + " ".join(f"{k} {v:.6f}" for k, v in sorted(by.items())))
    return 100.0 * spent / trace.busy_s

"""Kernels: the decode step's dense latent attention against its roofline
in the traced part of the window. Least time: what
``benchmark/kernels/mla_decode.py`` says the decode steps of the launches
dispatched in or just ahead of the traced span had to read and compute
(the engine's own count a launch, ``engine_mla_ctx_tokens_total``: a live
row's context a layer and step; the entry behind the device's four in a
launch's counters), times the steps whose events the trace holds; over
the device time of the decode program's events under the scope
``mla_dense_decode`` (``benchmark/lib/dsa_span.py``). A path that reads
pages past the rows' contexts, slots without a row, or a page twice reads
below its share."""

from benchmark.kernels import mla_decode
from benchmark.lib import dsa_span

SCOPE = "mla_dense_decode"
CTX_TOKENS = 9      # engine_mla_ctx_tokens_total in a launch's entry


def read(ctx):
    peaks, cfg = ctx.get("peaks"), ctx.get("cfg") or {}
    found = [s for s in dsa_span.segments(ctx) or [] if s.kind == "decode"]
    near = [(prev, e) for prev, e in dsa_span.launches_near_span(
        ctx, "decode", before_s=1.0) if prev is not None]
    spent = sum(s.seconds.get(SCOPE, 0.0) for s in found)
    steps = sum(e[2] for _, e in near)
    if not peaks or not spent or not steps \
            or len(near[0][1]) <= CTX_TOKENS:
        return None
    traced = sum(s.units for s in found)
    # the host's counter is a plain sum: no wrap to mind
    read_a_step = sum(e[CTX_TOKENS] - prev[CTX_TOKENS]
                      for prev, e in near) / steps
    least = mla_decode.least_seconds(traced * read_a_step, cfg, peaks)
    rows = sum(e[2] * e[3] for _, e in near) / steps
    print(f"mla_decode_roofline: seconds {spent:.6f} launches_near_span "
          f"{len(near)} their_steps {steps} traced_steps {traced:.2f} "
          f"rows_per_step {rows:.2f} ctx_tokens_per_row_layer_step "
          f"{read_a_step / (cfg['num_hidden_layers'] * max(rows, 1e-9)):.1f} "
          f"least_s {least:.6f}")
    return 100.0 * least / spent

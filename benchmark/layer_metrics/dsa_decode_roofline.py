"""Kernels: the decode step's selection and latent attention against
their roofline in the traced part of the window. Least time: what
``benchmark/kernels/dsa_decode.py`` says a decode step of the launches
dispatched in or just ahead of the traced span had to score and read (the
engine's own counts a launch, ``engine_dsa_scored_tokens_total`` and
``engine_dsa_selected_tokens_total``: a live row's context, and
``min(context, index_topk)`` of it, a layer and step; entries 3 and 4 of
a launch's counters), times the steps whose events the trace holds; over
the device time of the decode program's events under the scopes
``dsa_index_scores``, ``dsa_topk`` and ``mla_sparse_decode``
(``benchmark/lib/dsa_span.py``). A path that reads the whole latent
context and not the chosen tokens, or pages past the rows' contexts,
reads below its share."""

from benchmark.kernels import dsa_decode
from benchmark.lib import dsa_span


def read(ctx):
    peaks, cfg = ctx.get("peaks"), ctx.get("cfg") or {}
    found = [s for s in dsa_span.segments(ctx) or [] if s.kind == "decode"]
    near = [(prev, e) for prev, e in dsa_span.launches_near_span(
        ctx, "decode", before_s=1.0) if prev is not None]
    spent = sum(s.attention_s for s in found)
    steps = sum(e[2] for _, e in near)
    if not peaks or not spent or not steps or len(near[0][1]) < 10:
        return None
    traced = sum(s.units for s in found)
    # the host's counters are plain sums: no wrap to mind
    scored, selected = (sum(e[i] - prev[i] for prev, e in near) / steps
                        for i in (8, 9))
    least = dsa_decode.least_seconds(traced * scored, traced * selected, cfg,
                                     peaks)
    rows = sum(e[2] * e[3] for _, e in near) / steps
    by_scope = {}
    for s in found:
        for k, v in s.seconds.items():
            by_scope[k] = by_scope.get(k, 0.0) + v
    per = cfg["num_hidden_layers"] * max(rows, 1e-9)
    print(f"dsa_decode_roofline: seconds {spent:.6f} "
          + " ".join(f"{k} {v:.6f}" for k, v in sorted(by_scope.items()))
          + f" launches_near_span {len(near)} their_steps {steps} "
          f"traced_steps {traced:.2f} rows_per_step {rows:.2f} "
          f"scored_per_row_layer_step {scored / per:.1f} "
          f"selected_per_row_layer_step {selected / per:.1f} "
          f"least_s {least:.6f}")
    return 100.0 * least / spent

"""Kernels: the global layers' decode read's share of its byte roofline
in the traced part of the window. The trace names the Pallas kernel by
its ``name=`` (``paged_decode_qk<key width>``: events
``%paged_decode_qk192.N = ... custom-call(`` on the chip's ``XLA Ops``
line); each event is one global layer of one decode step, for that
step's live rows. Bytes: what ``benchmark/kernels/paged_decode_kv.py``
says the cached tokens a step reads have to move at the widths the model
states; the tokens are those of the decode launches dispatched inside
the traced span (``benchmark/lib/launch_span.py``: the engine's own
entry a launch, context lengths of live rows summed over its steps),
a step's share of them for each event; over the HBM peak; over the
events' device time. Without the kernel under that name, the launches'
entries, or a configuration whose value heads have a width of their own,
there is nothing to read."""

import re

from benchmark.kernels import paged_decode_kv
from benchmark.lib import launch_span

EVENT = re.compile(r"^%?paged_decode_qk\d+[.\d]* = ")


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    cfg = ctx.get("cfg") or {}
    work = (launch_span.span(ctx) or {}).get("decode")
    if trace is None or not peaks or not work or "v_head_dim" not in cfg:
        return None
    planes = trace.devices()
    if not planes:
        return None
    events = launch_span.events_by_kind(trace, planes[0], EVENT).get(
        "decode", [])
    seconds = sum(e.dur_ns for e in events) / 1e9
    if not seconds:
        return None
    ctx_tokens = work["tokens"] / work["units"]         # a step, so an event
    least = paged_decode_kv.least_seconds(
        ctx_tokens * len(events), cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"], peaks)
    print(f"paged_decode_roofline: events {len(events)} seconds "
          f"{seconds:.6f} span_launches {work['launches']} span_steps "
          f"{work['units']} span_ctx_tokens {work['tokens']} "
          f"rows_per_step {work['row_units'] / work['units']:.2f} "
          f"least_s {least:.6f}")
    return 100.0 * least / seconds

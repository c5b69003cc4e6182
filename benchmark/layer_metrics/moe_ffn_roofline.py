"""Kernels: the expert products' share of their roofline in the traced
part of the window. The chip's compiler turns ``lax.ragged_dot`` (under
the program's ``moe_expert_ffn`` scope) into a grouped kernel of its own
name: events ``%ragged-dot-none.N = ... custom-call(`` on the chip's
``XLA Ops`` line, three a layer and step or block (gate, up, down; the
``%ragged-dot-metadata`` events beside them are not products); a program
that spells the products out as dense ones has no such event and gives
nothing to read. Least time: what ``benchmark/kernels/moe_ffn.py`` says
the pairs computed and the experts visited need, over the chip's peaks;
pairs and visits are those the launches dispatched inside the traced
span counted on the device (``benchmark/lib/launch_span.py``), decode
chunks and cold prefills each apart (a step's few rows and a block's 256
are different work), each scaled to the layer-steps or layer-blocks
whose events the trace holds; over those events' device time. An
implementation that reads experts no row chose reads below the visited
share."""

import re

from benchmark.kernels import moe_ffn
from benchmark.lib import launch_span

# ``%ragged-dot-none.7 = bf16[384,2048]... custom-call(``: a product;
# ``%ragged-dot-metadata.2 = (s32[97]...`` beside it lays out its groups
EVENT = re.compile(r"^%?ragged-dot(?!-metadata)[\w.\-]* = ")
PER_LAYER_UNIT = 3        # gate, up, down


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    cfg = ctx.get("cfg") or {}
    work = launch_span.span(ctx)
    layers = sum(cfg.get("moe_layer_freq") or [0])
    if trace is None or not peaks or not work or not layers \
            or "moe_intermediate_size" not in cfg:
        return None
    planes = trace.devices()
    if not planes:
        return None
    events = launch_span.events_by_kind(trace, planes[0], EVENT)
    seconds = least = 0.0
    for kind, found in sorted(events.items()):
        w = work.get(kind)
        if not w or len(w["counters"]) < 2:
            continue
        traced = len(found) / PER_LAYER_UNIT / (layers * w["units"])
        pairs, visits = (traced * c for c in w["counters"][:2])
        kind_s = sum(e.dur_ns for e in found) / 1e9
        kind_least = moe_ffn.least_seconds(
            pairs, visits, cfg["hidden_size"], cfg["moe_intermediate_size"],
            peaks)
        print(f"moe_ffn_roofline: {kind} events {len(found)} seconds "
              f"{kind_s:.6f} span_launches {w['launches']} span_units "
              f"{w['units']} span_pairs {w['counters'][0]} span_visits "
              f"{w['counters'][1]} traced_share {traced:.4f} least_s "
              f"{kind_least:.6f}")
        seconds += kind_s
        least += kind_least
    if not seconds:
        return None
    return 100.0 * least / seconds

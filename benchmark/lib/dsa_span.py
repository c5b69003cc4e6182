"""What a traced span shows of learned sparse attention over a latent
cache (paddle_tpu/models/glm_moe_dsa.py), for the four ``dsa_*`` readers.

A device trace names an event by its compiled instruction (``%fusion.661
= ...``) and carries no scope, and the indexer, the selection and the
latent attention are the compiler's own fusions, sorts and gathers, so
no name of theirs can be known beforehand. With ``profile`` on (every
traced run) the engine reads each of its two paged programs' compiled
text once and gives ``stats()["scopes"]``: program -> {instruction:
named scope} for the scopes the model family lists
(``PagedPrograms.trace_scopes``); an event's instruction is looked up
there. A ``while`` or a ``conditional`` spans the operations of its body,
which the trace lists too: left out, so that nothing is counted twice.

A cold prefill of this family runs for seconds, so a traced span of three
often cuts one at either end, and a launch cut by the trace's start has
no ``XLA Modules`` event to say which program its operations belong to.
The span is therefore read as SEGMENTS: the operations inside each module
event (a launch that began inside the trace), those before the first
module (the tail of a launch that began before it) and those behind the
last module's end (the head of a launch the trace's end cut); a segment
without a module event belongs to the program whose table knows more of
its instructions. A program without the table (older than it, another
family, not profiled) gives ``None`` everywhere.

``DSA_SPAN_RECORD=<file>`` makes a traced run write what the readers were
handed beside the trace (the tables, the launches' entries, the span):
``benchmark/tests/record_scope_slice.py`` cuts the recorded slice of the
readers' tests from it."""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field

from . import launch_span, trace_reduce

PROGRAMS = {"decode": "jit_decode_chunk_paged", "prefill": "jit_prefill_paged"}
SELECTION = ("dsa_index_scores", "dsa_topk")
ATTENTION = ("mla_sparse_decode", "mla_prefill_attn")
INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")
# the grouped expert products, three a layer and step or block: by them
# a reader counts the layer-units whose events a segment holds
EXPERT_PRODUCT = re.compile(r"^%?ragged-dot(?!-metadata)[\w.\-]* = ")
PRODUCTS_PER_LAYER_UNIT = 3
BLOCK_ROWS = 256        # rows a block of the engine's cold prefill holds


@dataclass
class Segment:
    kind: str                   # decode | prefill
    part: str                   # whole | head | tail of its launch
    units: float = 0.0          # steps or blocks whose events it holds
    seconds: dict = field(default_factory=dict)     # scope -> seconds

    @property
    def attention_s(self):
        """Selection and latent attention, in seconds."""
        return sum(self.seconds.get(s, 0.0) for s in SELECTION + ATTENTION)


def _instruction(event):
    m = INSTRUCTION.match(event.name)
    return m.group(1) if m else None


def segments(ctx):
    """The traced span's launches of the two paged programs, in device
    order, each with the device seconds of its events by scope."""
    trace = ctx.get("trace")
    tables = (ctx.get("after") or {}).get("scopes")
    cfg = ctx.get("cfg") or {}
    if trace is None or not tables or "first_k_dense_replace" not in cfg:
        return None
    planes = trace.devices()
    if not planes:
        return None
    if os.environ.get("DSA_SPAN_RECORD"):
        with open(os.environ["DSA_SPAN_RECORD"], "w") as f:
            json.dump({"scopes": tables, "trace_span": ctx.get("trace_span"),
                       "launches": ctx["after"].get("launches")}, f)
    modules = sorted(trace.of(trace_reduce.MODULES_LINE, planes[0]),
                     key=lambda m: m.start_ns)
    ops = sorted(trace.of(trace_reduce.OPS_LINE, planes[0]),
                 key=lambda e: e.start_ns)
    runs, at = [], 0            # (module or None, part, its events)
    for i, m in enumerate(modules):
        kind = launch_span.KINDS.get(trace_reduce.short_name(m.name))
        before = []
        while at < len(ops) and ops[at].start_ns < m.start_ns:
            before.append(ops[at])
            at += 1
        if before and i == 0:
            runs.append((None, "tail", before))
        own = []
        while at < len(ops) and ops[at].start_ns < m.start_ns + m.dur_ns:
            own.append(ops[at])
            at += 1
        if kind is not None:
            runs.append((kind, "whole", own))
    if at < len(ops):
        runs.append((None, "tail" if not modules else "head", ops[at:]))
    moe_layers = max(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
                     1)
    out = []
    for kind, part, events in runs:
        names = [_instruction(e) for e in events]
        if kind is None:        # no module event: whose instructions?
            known = {k: sum(n in tables.get(p, {}) for n in names)
                     for k, p in PROGRAMS.items()}
            kind = max(known, key=known.get)
            if not known[kind]:
                continue
        seg = Segment(kind, part)
        table = tables.get(PROGRAMS[kind], {})
        products = 0
        for e, name in zip(events, names):
            products += bool(EXPERT_PRODUCT.match(e.name))
            scope = table.get(name)
            if scope is None or trace_reduce.label(e.name).split(" ")[0] \
                    in trace_reduce.CONTAINERS:
                continue
            seg.seconds[scope] = seg.seconds.get(scope, 0.0) + e.dur_ns / 1e9
        seg.units = products / PRODUCTS_PER_LAYER_UNIT / moe_layers
        out.append(seg)
    return out


def launches_near_span(ctx, kind, before_s):
    """The entries ``[t, kind, units, rows, tokens, *counters]`` of the
    launches of ``kind`` dispatched from ``before_s`` seconds ahead of
    the traced span to its end, each with the entry before it in the log
    (what its counters run on from; None for the log's first)."""
    log = (ctx.get("after") or {}).get("launches")
    lo_hi = ctx.get("trace_span")
    if not log or not lo_hi:
        return []
    return [(prev, e) for prev, e in zip([None, *log], log)
            if e[1] == kind and lo_hi[0] - before_s <= e[0] < lo_hi[1]]

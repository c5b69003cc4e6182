"""What the launches inside the traced part of a window computed.

A reader of a device trace sees how long a kernel's events took, not
what they were handed. With ``profile`` on (every traced run) the engine
keeps one entry a launch of its two paged programs and gives them in
``stats()["launches"]``: ``[t, kind, units, rows, tokens, *counters]``:
dispatched at ``t`` on ``time.perf_counter``; ``"decode"`` (``units``
steps of ``rows`` live rows that read ``tokens`` cached tokens in all)
or ``"prefill"`` (``units`` blocks of ``tokens`` prompt tokens); behind
them what the programs count on the device, running on from launch to
launch (int32, may wrap). ``span`` adds up the launches dispatched inside
the traced span, each kind apart; ``events_by_kind`` gives a kernel's
events by the program that launched them. A reader then scales a kind's
work by (units the trace shows) / (units the span's launches had): the
launches cut by the trace's two ends count for the part that was traced.
A program without the log (older than it, or not profiled) gives
``None``."""

from __future__ import annotations

import bisect

from . import trace_reduce

KINDS = {"jit_decode_chunk_paged": "decode", "jit_prefill_paged": "prefill"}


def span(ctx):
    """kind -> {"launches", "units", "row_units", "tokens", "counters"}
    over the launches dispatched inside ``ctx["trace_span"]``."""
    log = (ctx.get("after") or {}).get("launches")
    lo_hi = ctx.get("trace_span")
    if not log or not lo_hi:
        return None
    out, before = {}, None
    for t, kind, units, rows, tokens, *counters in log:
        if before is not None and lo_hi[0] <= t < lo_hi[1]:
            k = out.setdefault(kind, {"launches": 0, "units": 0,
                                      "row_units": 0, "tokens": 0,
                                      "counters": [0] * len(counters)})
            k["launches"] += 1
            k["units"] += units
            k["row_units"] += units * rows
            k["tokens"] += tokens
            k["counters"] = [c + (new - old) % (1 << 32) for c, new, old
                             in zip(k["counters"], counters, before)]
        before = counters
    return out or None


def events_by_kind(trace, plane, pattern):
    """kind -> the ``XLA Ops`` events of ``plane`` whose name matches
    ``pattern``, by the paged program whose launch they lie in; an event
    of a launch that began before the trace did belongs to none."""
    modules = sorted(trace.of(trace_reduce.MODULES_LINE, plane),
                     key=lambda m: m.start_ns)
    starts = [m.start_ns for m in modules]
    out = {}
    for e in trace.of(trace_reduce.OPS_LINE, plane):
        if not pattern.match(e.name):
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i < 0 or e.start_ns >= modules[i].start_ns + modules[i].dur_ns:
            continue
        kind = KINDS.get(trace_reduce.short_name(modules[i].name))
        if kind is not None:
            out.setdefault(kind, []).append(e)
    return out

"""The host's side of a traced run, on the device trace's clock.

With ``profile`` on (every traced run) the engine's ``StepProfiler``
opens a ``jax.profiler.TraceAnnotation`` round each phase of the serving
loop (``engine.poll``, ``engine.admission``, ``engine.prepare``,
``engine.launch``, ``engine.host_sync``, ``engine.account``,
``engine.publish``, ...) and round each step (``engine.step``), so the
run's ``.xplane.pb`` holds them as events of a host plane, on the line of
the serving thread, beside the device's operations. The ``launch``
annotation of the two paged programs carries the launch's identity as
arguments: ``launch`` (a count over the engine's life), ``kind``
(``decode`` | ``prefill``), ``units``, ``rows``, ``tokens`` and ``t_ns``
(``time.perf_counter`` in nanoseconds at entry: annotation start -
``t_ns`` puts ``perf_counter`` marks on the trace's timeline).

``of(ctx)`` matches those launches to the ``XLA Modules`` events of the
first chip, lays the chip's clock beside the host's by the runtime's own
events (``device_shift``), checks that the two then agree (``clock:``
line), puts
every idle interval of the chip down to the phase the serving thread
was in (``idle_by_phase:`` lines) gives the decode launches by live
rows (``launches:`` lines) and every other program by the phase it
began under (``programs:`` lines). A CPU trace, or a program that opens no
annotations, gives ``None``; nothing here raises for want of data.

The arithmetic takes plain lists of events, so that it can be tested on
a hand-built list and on a recorded slice (benchmark/tests/data).
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

from . import launch_span, trace_reduce
from .manifest import BENCH_DIR

PREFIX = "engine."
STEP = "step"
# loop order; whatever else the program names comes behind them
ORDER = ("poll", "admission", "prepare", "launch", "host_sync", "account",
         "publish")
NONE = "(none)"
# the TPU runtime's own host events (PJRT's threads): a program handed to
# the chip's queue, and the host's first notice that a program is done
ENQUEUED = ("DoEnqueueProgram",)
DONE = ("ReadSyncFlag", "tpu::System::Execute=>Done")
# a gap before a decode launch opens where one of these programs ends:
# the two that say what they were handed, and the prefix cache's prefill
PAGED = (*launch_span.KINDS, "jit_prefill_prefix")


@dataclass
class HostEvent:
    line: str                   # "<plane>#<index of the line>"
    name: str                   # the phase: "launch", "step", ...
    start_ns: float
    dur_ns: float
    args: dict = field(default_factory=dict)

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


def load(path):
    """The ``engine.*`` events of the host planes of one ``.xplane.pb``,
    with their arguments, and the runtime's ``ENQUEUED`` and ``DONE``
    events under their own names."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(HostEvent(f"{plane.name}#{i}",
                                         ev.name[len(PREFIX):],
                                         float(ev.start_ns),
                                         float(ev.duration_ns),
                                         dict(ev.stats)))
                elif ev.name in ENQUEUED or ev.name in DONE:
                    out.append(HostEvent(f"{plane.name}#{i}", ev.name,
                                         float(ev.start_ns),
                                         float(ev.duration_ns)))
    return out


def newest_trace():
    """``trace_reduce.reduce_dir``'s rule over every cell's directory:
    the run at hand wrote last."""
    root = BENCH_DIR.parent / "log" / "benchmark_trace"
    paths = sorted(glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def serving_line(host_events):
    """The events of the thread that launches: the line with the most
    ``launch`` annotations that say their kind, in time order."""
    count = {}
    for e in host_events:
        if e.name == "launch" and "kind" in e.args:
            count[e.line] = count.get(e.line, 0) + 1
    if not count:
        return []
    line = max(count, key=count.get)
    return sorted((e for e in host_events if e.line == line),
                  key=lambda e: (e.start_ns, -e.dur_ns))


def innermost(spans):
    """Disjoint (start, end, phase) pieces of the serving thread's time,
    each under the innermost phase open there (``step`` is no phase);
    time under no phase is left out."""
    marks = []
    for e in spans:
        if e.name != STEP and e.dur_ns > 0:
            marks.append((e.start_ns, 1, -e.dur_ns, e.name))
            marks.append((e.end_ns, 0, e.dur_ns, e.name))
    # at one instant closings go first, inner ones before outer ones;
    # then openings, outer ones before inner ones
    marks.sort()
    out, stack, at = [], [], None
    for t, opening, _, name in marks:
        if stack and t > at:
            out.append((at, t, stack[-1]))
        if opening:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        at = t
    return out


def overlap_by_phase(pieces, starts, lo, hi):
    """phase -> length of [lo, hi] under it; ``pieces`` as ``innermost``
    gives them, ``starts`` their starts."""
    out = {}
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(pieces) and pieces[i][0] < hi:
        a, b, name = pieces[i]
        cut = min(b, hi) - max(a, lo)
        if cut > 0:
            out[name] = out.get(name, 0.0) + cut
        i += 1
    return out


def match(spans, modules, ops):
    """The launches of the two paged programs whose annotation and
    module both lie in the trace: ``(annotation, module, host_sync,
    last operation's end)`` in time order. A module belongs to the last
    ``launch`` annotation of its kind that began before the module ended
    (a launch blocks the next of its kind, so with sound clocks: before
    it began; the slack lets the clock check see a module that starts
    before its annotation) and after the previous module of that kind
    began."""
    op_starts = [o.start_ns for o in ops]
    syncs = [e for e in spans if e.name == "host_sync"]
    sync_starts = [e.start_ns for e in syncs]
    out = []
    for program, kind in launch_span.KINDS.items():
        anns = [e for e in spans if e.name == "launch"
                and e.args.get("kind") == kind]
        ann_starts = [a.start_ns for a in anns]
        prev = None
        for m in (m for m in modules
                  if trace_reduce.short_name(m.name) == program):
            end = m.start_ns + m.dur_ns
            i = bisect.bisect_left(ann_starts, end) - 1
            if i >= 0 and (prev is None or ann_starts[i] > prev):
                a = anns[i]
                j = bisect.bisect_left(sync_starts, a.end_ns)
                lo = bisect.bisect_left(op_starts, m.start_ns)
                hi = bisect.bisect_left(op_starts, end)
                last = max((o.start_ns + o.dur_ns for o in ops[lo:hi]),
                           default=end)
                out.append((a, m, syncs[j] if j < len(syncs) else None,
                            last))
            prev = m.start_ns
    return sorted(out, key=lambda t: t[1].start_ns)


def device_shift(matched, host_events):
    """What to add to the device's times to put them on the host's
    clock, and how well that is known: ``(shift_ns, slack_ns)``. A trace
    lays the chip's clock beside the host's to a millisecond or two,
    another in every trace (seen on the v5e, PR 40: the chip up to 2.1 ms
    early), while the runtime's own host events bound each decode launch
    from both sides: its module cannot start before the program is handed
    to the chip's queue (``ENQUEUED``, the one such event between the
    launch annotation's start and its ``host_sync``'s end) nor end after
    the host first notices it is done (``DONE``, the first behind that).
    The shift is the middle of what all launches allow, the slack the
    width of it (negative: they contradict each other). Without such
    events: ``(0, None)``."""
    enqueued = sorted((e.start_ns, e.end_ns) for e in host_events
                      if e.name in ENQUEUED)
    done = sorted(e.start_ns for e in host_events if e.name in DONE)
    lo = hi = None
    for a, m, sync, _ in matched:
        if a.args.get("kind") != "decode" or sync is None:
            continue
        i = bisect.bisect_left(enqueued, (a.start_ns,))
        if i == len(enqueued) or enqueued[i][0] >= sync.end_ns:
            continue
        j = bisect.bisect_left(done, enqueued[i][1])
        if j == len(done):
            continue
        least = enqueued[i][0] - m.start_ns
        most = done[j] - (m.start_ns + m.dur_ns)
        lo = least if lo is None else max(lo, least)
        hi = most if hi is None else min(hi, most)
    if lo is None:
        return 0.0, None
    return (lo + hi) / 2, hi - lo


def clock_check(matched):
    """How well the host's annotations and the device's events share a
    clock: the share of matched launches whose module starts before its
    annotation, or whose ``host_sync`` ends before the module's last
    operation does (both 0 on one clock), and the spread of
    (annotation start - ``t_ns``) over the launches."""
    bad = sum(1 for a, m, sync, last in matched
              if m.start_ns < a.start_ns
              or (sync is not None and sync.end_ns < last))
    offs = sorted(a.start_ns - a.args["t_ns"] for a, *_ in matched
                  if "t_ns" in a.args)
    return {"launches": len(matched),
            "out_of_order": bad / len(matched),
            "offset_ns": offs[len(offs) // 2] if offs else None,
            "spread_us": (offs[-1] - offs[0]) / 1e3 if offs else None}


@dataclass
class HostSpans:
    clock: dict
    n_decode: int               # matched decode launches with a gap read
    gap_ns: dict                # phase -> idle ns before decode launches
    idle_ns: dict               # phase -> idle ns of the whole span
    by_rows: dict               # live rows -> [device ns a step, ...]
    step_ns: list               # engine.step spans that hold a decode launch
    others: dict                # (program, phase it began under) -> [ns, ...]

    def gap_ms(self, *phases):
        """Mean idle ms before a decode launch: all of it, or the part
        under ``phases``."""
        if not self.n_decode:
            return None
        ns = sum(self.gap_ns.values()) if not phases else \
            sum(self.gap_ns.get(p, 0.0) for p in phases)
        return ns / self.n_decode / 1e6

    def attributed_share(self):
        total = sum(self.idle_ns.values())
        if not total:
            return None
        return 1.0 - self.idle_ns.get(NONE, 0.0) / total

    def lines(self):
        c = {k: float("nan") if v is None else v
             for k, v in self.clock.items()}
        yield (f"clock: launches {c['launches']} out_of_order "
               f"{c['out_of_order']:.4f} spread_us {c['spread_us']:.3f} "
               f"offset_ns {c['offset_ns']:.0f} device_shift_us "
               f"{c['device_shift_us']:.1f} slack_us {c['slack_us']:.1f}")
        total = sum(self.idle_ns.values()) or 1.0
        names = [p for p in ORDER if p in self.idle_ns or p in self.gap_ns]
        names += sorted((set(self.idle_ns) | set(self.gap_ns))
                        - set(names) - {NONE}) + [NONE]
        for p in names:
            yield (f"idle_by_phase: {p} seconds_in_span "
                   f"{self.idle_ns.get(p, 0.0) / 1e9:.6f} "
                   f"ms_a_decode_launch "
                   f"{self.gap_ns.get(p, 0.0) / max(self.n_decode, 1) / 1e6:.4f}"
                   f" share_of_idle {self.idle_ns.get(p, 0.0) / total:.4f}")
        yield (f"idle_by_phase: all seconds_in_span {total / 1e9:.6f} "
               f"ms_a_decode_launch {self.gap_ms() or 0.0:.4f} "
               f"decode_launches {self.n_decode}")
        for rows in sorted(self.by_rows):
            v = self.by_rows[rows]
            yield (f"launches: decode rows {rows} count {len(v)} "
                   f"device_ms_a_step {sum(v) / len(v) / 1e6:.4f}")
        for (program, phase), v in sorted(self.others.items()):
            yield (f"programs: {program} began_under {phase} count {len(v)} "
                   f"device_s {sum(v) / 1e9:.6f}")


def analyse(host_events, device_events):
    """``HostSpans`` of one chip's ``XLA Ops`` and ``XLA Modules`` events
    and the host's ``engine.*`` events, or ``None`` where either is
    missing."""
    trace = trace_reduce.Reduced(device_events)
    planes = trace.devices()
    spans = serving_line(host_events)
    if not planes or not spans:
        return None
    ops = sorted(trace.of(trace_reduce.OPS_LINE, planes[0]),
                 key=lambda e: e.start_ns)
    modules = sorted(trace.of(trace_reduce.MODULES_LINE, planes[0]),
                     key=lambda e: e.start_ns)
    matched = match(spans, modules, ops)
    if not ops or not matched:
        return None
    shift, slack = device_shift(matched, host_events)
    if shift:
        ops, modules = ([trace_reduce.Event(e.plane, e.line, e.name,
                                            e.start_ns + shift, e.dur_ns)
                         for e in events] for events in (ops, modules))
        matched = match(spans, modules, ops)
    busy = [(o.start_ns, o.start_ns + o.dur_ns) for o in ops]
    lo, hi = busy[0][0], max(b for _, b in busy)
    idle = trace_reduce.gaps_ns(busy, lo, hi)
    idle_starts = [a for a, _ in idle]
    pieces = innermost(spans)
    piece_starts = [p[0] for p in pieces]

    def by_phase(a, b):
        """phase -> idle ns inside [a, b]."""
        out = {}
        i = max(0, bisect.bisect_right(idle_starts, a) - 1)
        while i < len(idle) and idle[i][0] < b:
            g0, g1 = max(idle[i][0], a), min(idle[i][1], b)
            if g1 > g0:
                named = overlap_by_phase(pieces, piece_starts, g0, g1)
                named[NONE] = (g1 - g0) - sum(named.values())
                for k, v in named.items():
                    out[k] = out.get(k, 0.0) + v
            i += 1
        return out

    paged, others = [], {}
    for m in modules:
        program = trace_reduce.short_name(m.name)
        if program in PAGED:
            paged.append(m)
        else:
            # the phase the serving thread was in when the program began
            # on the chip (it was dispatched then or, the chip busy, before)
            i = bisect.bisect_right(piece_starts, m.start_ns) - 1
            phase = pieces[i][2] if i >= 0 and m.start_ns < pieces[i][1] \
                else NONE
            others.setdefault((program, phase), []).append(m.dur_ns)
    paged_starts = [m.start_ns for m in paged]
    steps = [e for e in spans if e.name == STEP]
    step_starts = [e.start_ns for e in steps]
    gap_ns, by_rows, step_ns, n = {}, {}, [], 0
    for a, m, _, _ in matched:
        if a.args.get("kind") != "decode":
            continue
        units = a.args.get("units") or 1
        by_rows.setdefault(a.args.get("rows"), []).append(m.dur_ns / units)
        s = bisect.bisect_right(step_starts, a.start_ns) - 1
        if s >= 0 and steps[s].end_ns >= a.end_ns:
            step_ns.append(steps[s].dur_ns)
        i = bisect.bisect_left(paged_starts, m.start_ns) - 1
        if i < 0:
            continue            # nothing before it in the trace
        n += 1
        for k, v in by_phase(paged[i].start_ns + paged[i].dur_ns,
                             m.start_ns).items():
            gap_ns[k] = gap_ns.get(k, 0.0) + v
    clock = {**clock_check(matched), "device_shift_us": shift / 1e3,
             "slack_us": None if slack is None else slack / 1e3}
    return HostSpans(clock, n, gap_ns, by_phase(lo, hi), by_rows, step_ns,
                     others)


def of(ctx):
    """The run's ``HostSpans``, read once and kept in ``ctx`` for the
    readers that follow; ``ctx["host_events"]`` (the tests') stands in
    for the trace file."""
    if "host_spans" not in ctx:
        trace = ctx.get("trace")
        spans = None
        if trace is not None and trace.devices():
            host = ctx.get("host_events")
            if host is None:
                path = newest_trace()
                host = load(path) if path else []
            spans = analyse(host, trace.events)
            for line in spans.lines() if spans else ():
                print(line)
        ctx["host_spans"] = spans
    return ctx["host_spans"]

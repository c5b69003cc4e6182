"""From a profiler trace to numbers. ``load`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote into plain events; ``Reduced`` holds them
and answers what the readers ask: the union of the intervals in which
an operation ran on each device, time by operation and by program, the
longest gaps and what the host was doing in them.

Kept apart so that the arithmetic can be tested on a small recorded
list of events (benchmark/tests/data) without a trace file or a chip.

What a TPU trace of JAX 0.9 looks like (seen on the v5e, PR 23): one
plane per chip named ``/device:TPU:<n>``; its line ``XLA Ops`` has one
event per executed HLO operation (fusions, custom calls, collectives),
``XLA Modules`` one per launched program, named ``jit_<function>(<id>)``;
``Steps`` repeats the modules. Host threads are planes ``/host:CPU``
with one line per thread.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(path, host_lines=()):
    """Events of the device planes' op and module lines, and of the host
    lines whose name is in ``host_lines``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE) \
                    or not device and line.name in host_lines:
                for ev in line.events:
                    events.append(Event(plane.name, line.name, ev.name,
                                        float(ev.start_ns),
                                        float(ev.duration_ns)))
    return events


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_ns(intervals, lo, hi):
    """The idle gaps (start, end) inside [lo, hi] left by intervals."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return out


def label(name):
    """A short, stable label of an HLO operation's trace name:
    ``%copy.80 = bf16[14,4141,4,16,128]{...} copy(...)`` ->
    ``copy bf16[14,4141,4,16,128]``; a program's name loses its id."""
    m = re.match(r"%[\w.\-]+ = \(?(\w+\[[\d,]*\])[^ ]* ?.*? ([\w\-]+)\(", name)
    if m:
        return f"{m.group(2)} {m.group(1)}"
    return short_name(name)[:80]


def short_name(name):
    """``jit_decode_chunk_paged(123)`` -> ``jit_decode_chunk_paged``;
    ``fusion.123`` -> ``fusion``: what stays the same from run to run."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.\d]+$", "", name) or name


@dataclass
class Reduced:
    events: list = field(default_factory=list)

    def devices(self):
        return sorted({e.plane for e in self.events
                       if e.plane.startswith("/device:")})

    def of(self, line, plane=None):
        return [e for e in self.events if e.line == line
                and (plane is None or e.plane == plane)]

    def _span(self):
        ops = self.of(OPS_LINE)
        if not ops:
            return None
        return (min(e.start_ns for e in ops),
                max(e.start_ns + e.dur_ns for e in ops))

    @property
    def window_s(self):
        """From the first device operation of the trace to the last."""
        span = self._span()
        return 0.0 if span is None else (span[1] - span[0]) / 1e9

    @property
    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        planes = self.devices()
        if not planes:
            return 0.0
        busy = [union_ns([(e.start_ns, e.start_ns + e.dur_ns)
                          for e in self.of(OPS_LINE, p)]) for p in planes]
        return sum(busy) / len(busy) / 1e9

    def idle_share(self):
        return None if not self.window_s else 1.0 - self.busy_s / self.window_s

    def seconds_by(self, line, key=short_name, plane=None):
        """name -> device seconds on one chip (the first by default)."""
        planes = self.devices()
        if not planes:
            return {}
        out = {}
        for e in self.of(line, plane or planes[0]):
            k = key(e.name)
            out[k] = out.get(k, 0.0) + e.dur_ns / 1e9
        return out

    def matching_seconds(self, line, pattern, plane=None):
        rx = re.compile(pattern)
        return sum(s for name, s in
                   self.seconds_by(line, key=lambda n: n, plane=plane).items()
                   if rx.search(name))

    def exposed_seconds(self, is_collective, plane=None):
        """Seconds in which a collective ran on the chip and no other
        operation did."""
        planes = self.devices()
        if not planes:
            return 0.0
        ops = self.of(OPS_LINE, plane or planes[0])
        coll = [(e.start_ns, e.start_ns + e.dur_ns) for e in ops
                if is_collective(e.name)]
        rest = [(e.start_ns, e.start_ns + e.dur_ns) for e in ops
                if not is_collective(e.name)]
        both = union_ns(coll + rest)
        return (both - union_ns(rest)) / 1e9

    def breakdown(self, n=10):
        """The operations that took most device time and the longest
        idle gaps, named by the program that ran after the gap."""
        planes = self.devices()
        if not planes:
            return {"device_ops": [], "idle_gaps": []}
        # a while or a conditional spans the operations of its body, which
        # the trace lists too: left out, so that nothing is counted twice
        ops = sorted(((k, v) for k, v in
                      self.seconds_by(OPS_LINE, key=label).items()
                      if k.split(" ")[0] not in CONTAINERS),
                     key=lambda kv: -kv[1])[:n]
        p = planes[0]
        span = self._span()
        mods = sorted(self.of(MODULES_LINE, p), key=lambda e: e.start_ns)
        gaps = gaps_ns([(e.start_ns, e.start_ns + e.dur_ns)
                        for e in self.of(OPS_LINE, p)], *span)
        starts = [m.start_ns for m in mods]
        by = {}
        for a, b in gaps:
            # the program that runs once the gap ends: the first whose
            # start is not before it (1 us of slack for the op's own start)
            i = bisect.bisect_left(starts, b - 1e3)
            nxt = short_name(mods[i].name) if i < len(mods) else "end_of_trace"
            by["before " + nxt] = by.get("before " + nxt, 0.0) + (b - a) / 1e9
        idle = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce_dir(trace_dir):
    """The newest trace under ``trace_dir``, reduced."""
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return Reduced([])
    return Reduced(load(paths[-1]))

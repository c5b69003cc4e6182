"""paddle_tpu.profiler — host spans + device tracing (reference:
python/paddle/profiler/profiler.py — Profiler:346, ProfilerState:79,
export_chrome_tracing:215; C++ host tracer platform/profiler/profiler.h:47
with RecordEvent spans and a CUPTI device tracer merged into one timeline).

TPU-native split:
- host spans: ``RecordEvent`` context manager into a process-global ring
  buffer; ops auto-annotated at dispatch via core.dispatch.OP_OBSERVERS
  (the reference annotates kernels at dispatch the same way);
- device timeline: ``jax.profiler`` xplane trace (TensorBoard-viewable),
  started/stopped with the profiler when ``trace_dir`` is set — XLA's
  profiler is the CUPTI analogue;
- exports: chrome-trace JSON of the host spans + a stats summary table
  (reference profiler_statistic.py).
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from enum import Enum

from .timer import Benchmark, benchmark  # noqa: F401

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "Benchmark", "benchmark"]


class ProfilerState(Enum):
    """reference profiler.py:79."""

    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 2
    TPU = 3


@dataclass
class _Span:
    name: str
    start_ns: int
    end_ns: int
    tid: int
    kind: str = "user"
    worker: str | None = None   # fleet worker lane (ISSUE 5 export)


class _SpanBuffer:
    """Process-global span store (reference host_event_recorder.h ring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans: list[_Span] = []
        self.enabled = False

    def add(self, span):
        with self._lock:
            self.spans.append(span)

    def drain(self):
        with self._lock:
            out = self.spans
            self.spans = []
            return out


_BUFFER = _SpanBuffer()


class RecordEvent:
    """reference python/paddle/profiler/utils.py RecordEvent — host span;
    usable as context manager or begin()/end() pair."""

    def __init__(self, name: str, event_type: str = "user",
                 worker: str | None = None):
        self.name = name
        self.event_type = event_type
        self.worker = worker        # fleet worker attribution (ISSUE 5)
        self._start = None

    def begin(self):
        self._start = time.perf_counter_ns()

    def end(self):
        if self._start is None or not _BUFFER.enabled:
            self._start = None
            return
        _BUFFER.add(_Span(self.name, self._start, time.perf_counter_ns(),
                          threading.get_ident(), self.event_type,
                          self.worker))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0):
    """reference profiler.py make_scheduler — step → ProfilerState."""

    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    """reference profiler.py:215 — on_trace_ready factory writing
    chrome://tracing JSON."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        handler._serial = getattr(handler, "_serial", 0) + 1
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time())}_"
            f"{handler._serial}.paddle_trace.json")
        prof._export_chrome(path)
        return path

    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class Profiler:
    """reference profiler.py Profiler:346.

    with profiler.Profiler(on_trace_ready=export_chrome_tracing('./log'))
    as p:
        for batch in loader:
            train_step(batch)
            p.step()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 trace_dir: str | None = None, timer_only: bool = False,
                 record_shapes: bool = False, profile_memory: bool = False):
        if scheduler is None:
            self._scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            # the window's last step must be RECORD_AND_RETURN so
            # on_trace_ready fires when the window closes (reference maps
            # the tuple form the same way)
            self._scheduler = lambda step: (
                ProfilerState.RECORD_AND_RETURN if step == end - 1
                else ProfilerState.RECORD if start <= step < end
                else ProfilerState.CLOSED)
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._trace_dir = trace_dir
        self._timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._spans: list[_Span] = []
        self._op_counts: dict[str, int] = {}
        self._observer = None
        self._device_tracing = False
        self.benchmark = Benchmark()

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        self.state = self._scheduler(self.step_num)
        if self.state in (ProfilerState.RECORD,
                          ProfilerState.RECORD_AND_RETURN):
            self._enable()
        self.benchmark.begin()
        return self

    def stop(self):
        was_recording = _BUFFER.enabled
        if was_recording:
            self._collect()
        self._disable()
        self.benchmark.end()
        # export only when a live recording window is being closed here —
        # RECORD_AND_RETURN windows already exported in step(), and a
        # fully-CLOSED run has nothing to write
        if was_recording and self._on_trace_ready is not None \
                and not self._timer_only:
            self._on_trace_ready(self)

    def step(self, num_samples: int | None = None):
        """Advance the scheduler one iteration (reference Profiler.step)."""
        self.benchmark.step(num_samples)
        prev = self.state
        self.step_num += 1
        self.state = self._scheduler(self.step_num)
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if prev in recording:
            self._collect()
        if prev == ProfilerState.RECORD_AND_RETURN \
                and self._on_trace_ready is not None:
            self._on_trace_ready(self)
            # each window exports its own events only
            self._spans = []
            self._op_counts = {}
        if self.state in recording and not _BUFFER.enabled:
            self._enable()
        elif self.state not in recording and _BUFFER.enabled:
            self._disable()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- internals ----------------------------------------------------------
    def _enable(self):
        _BUFFER.enabled = True
        if self._observer is None:
            from ..core.dispatch import OP_OBSERVERS

            def obs(name):
                now = time.perf_counter_ns()
                _BUFFER.add(_Span(name, now, now, threading.get_ident(),
                                  "op"))
            self._observer = obs
            OP_OBSERVERS.append(obs)
        if self._trace_dir and not self._device_tracing:
            import jax
            try:
                jax.profiler.start_trace(self._trace_dir)
                self._device_tracing = True
            except Exception:  # noqa: BLE001 — device tracing best-effort
                self._device_tracing = False

    def _disable(self):
        _BUFFER.enabled = False
        if self._observer is not None:
            from ..core.dispatch import OP_OBSERVERS
            if self._observer in OP_OBSERVERS:
                OP_OBSERVERS.remove(self._observer)
            self._observer = None
        if self._device_tracing:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                self._device_tracing = False

    def _collect(self):
        spans = _BUFFER.drain()
        self._spans.extend(spans)
        for s in spans:
            if s.kind == "op":
                self._op_counts[s.name] = self._op_counts.get(s.name, 0) + 1

    # -- outputs ------------------------------------------------------------
    def _export_chrome(self, path: str):
        events = []
        for s in self._spans:
            if s.kind == "op":
                events.append({"name": s.name, "ph": "i",
                               "ts": s.start_ns / 1e3, "pid": os.getpid(),
                               "tid": s.tid, "s": "t", "cat": "op"})
            else:
                # cat carries the span kind ("user", "engine", ...), so
                # the serving lifecycle spans the DecodeEngine emits
                # render as their own category in one unified timeline
                # next to op-dispatch instants (ISSUE 3)
                events.append({"name": s.name, "ph": "X",
                               "ts": s.start_ns / 1e3,
                               "dur": (s.end_ns - s.start_ns) / 1e3,
                               "pid": os.getpid(), "tid": s.tid,
                               "cat": s.kind})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def export_chrome_tracing(self, path: str):
        return self._export_chrome(path)

    export = export_chrome_tracing

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated span statistics (reference profiler_statistic.py)."""
        agg: dict[str, list[float]] = {}
        for s in self._spans:
            if s.kind == "op":
                continue
            dur = (s.end_ns - s.start_ns) / 1e6
            rec = agg.setdefault(s.name, [0, 0.0, float("inf"), 0.0])
            rec[0] += 1
            rec[1] += dur
            rec[2] = min(rec[2], dur)
            rec[3] = max(rec[3], dur)
        lines = [f"{'Name':<32}{'Calls':>8}{'Total(ms)':>12}"
                 f"{'Avg(ms)':>12}{'Min(ms)':>12}{'Max(ms)':>12}",
                 "-" * 88]
        for name, (cnt, tot, mn, mx) in sorted(
                agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<32}{cnt:>8}{tot:>12.3f}"
                         f"{tot / cnt:>12.3f}{mn:>12.3f}{mx:>12.3f}")
        if self._op_counts:
            lines.append("-" * 88)
            lines.append("Op dispatch counts:")
            for name, cnt in sorted(self._op_counts.items(),
                                    key=lambda kv: -kv[1])[:40]:
                lines.append(f"  {name:<38}{cnt:>8}")
        table = "\n".join(lines)
        print(table)
        return {"events": {k: {"calls": v[0], "total_ms": v[1],
                               "min_ms": v[2], "max_ms": v[3]}
                           for k, v in agg.items()},
                "op_counts": dict(self._op_counts)}


class SortedKeys(Enum):
    """Sort keys for summary tables (reference: profiler/profiler_statistic.py
    SortedKeys)."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Summary table views (reference: profiler/profiler.py SummaryView)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name: str, worker_name: str | None = None):
    """Protobuf-dump exporter (reference: profiler.py export_protobuf).
    The TPU build's interchange format is the chrome trace; this emits the
    same span payload serialized with pickle (protobuf schema owned by the
    reference's C++ tracer doesn't exist here) under .pb naming for
    tooling parity."""
    import os
    import pickle
    import socket
    import time

    def handle(prof):
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{socket.gethostname()}"
        path = os.path.join(dir_name,
                            f"{worker}_{time.strftime('%Y%m%d%H%M%S')}.pb")
        with open(path, "wb") as f:
            pickle.dump([s.__dict__ for s in prof._spans], f)
        return path

    return handle


__all__ += ["SortedKeys", "SummaryView", "export_protobuf"]

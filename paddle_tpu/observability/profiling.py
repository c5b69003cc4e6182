"""Step-phase profiler + recompile observatory (ISSUE 13 tentpole).

Two runtime instruments for the serving step loop, both OFF by default
(``DecodeEngine(profile=None)`` pays nothing and keeps r15 outputs
bit-identical):

- :class:`StepProfiler` — a low-overhead per-step phase timer. The
  engine wraps each phase of a step (:data:`PHASES`: poll / admission /
  schedule / prefill-chunk / spec-draft / prepare / launch / host-sync /
  account / publish / telemetry) in a prebuilt context-manager span;
  durations land in fixed-size rings keyed by the injected
  ``observability.now`` clock. Every span, and the step, also opens a
  ``jax.profiler.TraceAnnotation`` (``engine.<phase>``, ``engine.step``),
  so that a device trace taken meanwhile holds the host's phases on its
  own clock; a span given arguments at entry (a launch's identity)
  writes them on its annotation with ``t_ns``, ``observability.now`` at
  entry in nanoseconds, which ties the ``RequestTrace`` marks (that
  clock's) to the trace's timeline.
  ``summary()`` computes per-phase p50/p99 through the shared
  :func:`~paddle_tpu.observability.metrics.quantile_from_buckets`
  bucket math; ``to_events()`` emits chrome ``ph="X"`` slices in the
  same perf_counter-µs timebase as the r10 trace/span lanes, so
  ``ServingFleet.export_chrome_timeline`` can merge a per-worker
  profile lane beside them. An EWMA of step wall time flags outlier
  steps into the flight ring — the postmortem sees WHICH steps went
  long, not just that p99 moved.

- :class:`CompileTracker` — the runtime twin of graftcheck's static
  SC06 recompile-hazard checker. Every compiled-program build site
  wraps its callable in :meth:`CompileTracker.wrap`; a first-seen
  abstract signature (leaf shapes + dtypes) counts as one compilation
  and is recorded (program name, signature, bucket key, wall time —
  the first call's wall is the compile proxy) into a bounded
  ``compile_log`` ring plus ``engine_compiles_total``. After
  :meth:`warmup_done`, further first-seen signatures are UNEXPECTED:
  they bump an SLO-attachable ``engine_unexpected_compiles`` gauge
  (rule stat ``"value"``) and land in the flight ring — the stray
  unbucketed shape that SC06 can only catch lexically becomes a
  runtime alarm.
"""

from __future__ import annotations

import logging
import re
import threading
from collections import deque

from ..utils.log import get_logger, log_kv
from .metrics import DEFAULT_LATENCY_BUCKETS, now, quantile_from_buckets

__all__ = ["PHASES", "StepProfiler", "CompileTracker"]

_log = get_logger("paddle_tpu.observability.profiling")

#: canonical step-phase vocabulary (ISSUE 13), in loop order — the
#: server owns poll, the engine admission..publish (a prefill's own launch
#: lies inside admission; the read of its first token is a host_sync of
#: the step, behind the decode launch), the fleet router schedule +
#: telemetry
PHASES = ("poll", "admission", "schedule", "prefill_chunk", "spec_draft",
          "prepare", "launch", "host_sync", "account", "publish",
          "telemetry")


def _annotation(name, **args):
    """The host span a ``jax.profiler`` trace holds beside the device's
    operations (a no-op while nothing traces). Imported late: the
    package has no import-time dependency."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **args)


class _PhaseSpan:
    """Prebuilt, reusable (non-reentrant) timing context for ONE phase.
    ``args``, set by :meth:`StepProfiler.phase` for the next entry, go
    on the trace annotation with ``t_ns``."""

    __slots__ = ("_prof", "_name", "_label", "_t0", "_args", "_ann")

    def __init__(self, prof, name):
        self._prof = prof
        self._name = name
        self._label = "engine." + name
        self._t0 = 0.0
        self._args = self._ann = None

    def __enter__(self):
        args, self._args = self._args, None
        self._ann = _annotation(self._label) if args is None else \
            _annotation(self._label, t_ns=int(now() * 1e9), **args)
        self._ann.__enter__()
        self._t0 = self._prof._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._prof._observe_phase(self._name, self._t0)
        self._ann.__exit__(exc_type, exc, tb)
        return False


class StepProfiler:
    """Fixed-ring per-step phase timer for one engine (or the fleet
    router). All rings are bounded (``capacity`` newest entries); the
    scrape side (``summary()``/``to_events()``) copies under the lock
    and computes outside it."""

    def __init__(self, capacity: int = 256, clock=None, registry=None,
                 recorder=None, worker_id=None, outlier_factor=4.0,
                 outlier_min_steps: int = 16):
        self.worker_id = worker_id
        self.capacity = int(capacity)
        self._clock = now if clock is None else clock
        self.recorder = recorder
        self._outlier_factor = float(outlier_factor)
        self._outlier_min = int(outlier_min_steps)
        self._lock = threading.Lock()
        self._rings = {}                      # guarded-by: _lock
        for p in PHASES:
            self._rings[p] = deque(maxlen=self.capacity)
        self._steps: deque = deque(maxlen=self.capacity)  # guarded-by: _lock
        self._step_idx = 0                    # guarded-by: _lock
        self._t_step0 = None                  # guarded-by: _lock
        self._ewma = None                     # guarded-by: _lock
        self._spans = {p: _PhaseSpan(self, p) for p in PHASES}
        self._step_ann = None                 # serving thread only
        self._h_phase = self._c_outliers = None
        if registry is not None:
            self._h_phase = registry.histogram(
                "engine_step_phase_seconds",
                "wall time of individual engine step phases")
            self._c_outliers = registry.counter(
                "engine_step_outliers_total",
                "profiled steps whose wall exceeded the EWMA bound")
            registry.gauge(
                "engine_profiled_steps",
                "steps recorded by the step profiler", fn=self._n_steps)
            registry.gauge(
                "engine_step_wall_ewma_seconds",
                "EWMA of profiled step wall time", fn=self._ewma_value)

    # fn-gauge callbacks run on the scrape thread with no caller locks
    def _n_steps(self) -> int:
        with self._lock:
            return self._step_idx

    def _ewma_value(self) -> float:
        with self._lock:
            return 0.0 if self._ewma is None else self._ewma

    # -- hot path -----------------------------------------------------------
    def phase(self, name: str, args=None) -> _PhaseSpan:
        """The prebuilt span for ``name`` — ``with prof.phase("launch"):``;
        ``args`` (a dict) is written on this entry's trace annotation."""
        span = self._spans[name]
        span._args = args
        return span

    def _observe_phase(self, name, t0) -> None:
        dur = self._clock() - t0
        with self._lock:
            self._rings[name].append((self._step_idx + 1, t0, dur))
        if self._h_phase is not None:
            self._h_phase.observe(dur)

    def begin_step(self) -> None:
        self._step_ann = _annotation("engine.step")
        self._step_ann.__enter__()
        with self._lock:
            self._t_step0 = self._clock()

    def end_step(self):
        """Close the step ring entry; returns the step wall (None if
        no ``begin_step`` was pending). Outlier steps (wall beyond
        ``outlier_factor`` × the EWMA, after ``outlier_min_steps``
        warmup) are flagged into the flight ring."""
        ann, self._step_ann = self._step_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        with self._lock:
            t0 = self._t_step0
            if t0 is None:
                return None
            self._t_step0 = None
            wall = self._clock() - t0
            prev = self._ewma
            self._step_idx += 1
            idx = self._step_idx
            self._steps.append((idx, t0, wall))
            self._ewma = wall if prev is None \
                else 0.8 * prev + 0.2 * wall
            outlier = (prev is not None and idx > self._outlier_min
                       and wall > self._outlier_factor * prev)
        if outlier:
            if self._c_outliers is not None:
                self._c_outliers.inc()
            if self.recorder is not None:
                self.recorder.record(
                    "phase_outlier", worker=self.worker_id, step=idx,
                    wall_s=round(wall, 6), ewma_s=round(prev, 6))
        return wall

    # -- scrape side --------------------------------------------------------
    @staticmethod
    def _stats(durs) -> dict:
        """count/total/p50/p99/max of a duration list through the
        shared cumulative-bucket quantile rule (same edges as every
        latency histogram, so profile summaries and SLO windows agree
        on what 'p99' means)."""
        if not durs:
            return {"count": 0, "total_s": 0.0, "p50_s": 0.0,
                    "p99_s": 0.0, "max_s": 0.0}
        ordered = sorted(durs)
        buckets = {}
        i = 0
        for edge in list(DEFAULT_LATENCY_BUCKETS) + [float("inf")]:
            while i < len(ordered) and ordered[i] <= edge:
                i += 1
            buckets[edge] = i
        mx = ordered[-1]
        return {"count": len(durs), "total_s": round(sum(durs), 6),
                "p50_s": quantile_from_buckets(0.5, buckets,
                                               len(durs), mx),
                "p99_s": quantile_from_buckets(0.99, buckets,
                                               len(durs), mx),
                "max_s": mx}

    def summary(self) -> dict:
        """JSON-able per-phase digest over the rings (the newest
        ``capacity`` entries)."""
        with self._lock:
            rings = {p: [d for _, _, d in r]
                     for p, r in self._rings.items()}
            walls = [w for _, _, w in self._steps]
            idx = self._step_idx
            ewma = self._ewma
        phases = {p: self._stats(rings[p]) for p in PHASES
                  if rings[p]}
        return {"worker": self.worker_id, "steps": idx,
                "window": len(walls),
                "step_wall": self._stats(walls),
                "ewma_s": 0.0 if ewma is None else round(ewma, 6),
                "phases": phases}

    def to_events(self, pid: int = 0) -> list:
        """Chrome ``ph="X"`` slices — step wall on tid 0, phases on
        tid 1 — in perf_counter microseconds, the same timebase as the
        profiler op spans and trace lanes they merge beside."""
        with self._lock:
            rings = {p: list(r) for p, r in self._rings.items()}
            steps = list(self._steps)
        evts = []
        for idx, t0, wall in steps:
            evts.append({"name": "engine.step", "cat": "profile",
                         "ph": "X", "ts": t0 * 1e6, "dur": wall * 1e6,
                         "pid": pid, "tid": 0, "args": {"step": idx}})
        for p in PHASES:
            for idx, t0, dur in rings[p]:
                evts.append({"name": p, "cat": "profile", "ph": "X",
                             "ts": t0 * 1e6, "dur": dur * 1e6,
                             "pid": pid, "tid": 1,
                             "args": {"step": idx}})
        return evts


_HLO_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_map(hlo_text, scopes):
    """instruction name -> the innermost of ``scopes`` (names given to
    ``jax.named_scope``) its ``op_name`` lies under, over the instructions
    of a compiled program's text that run as operations of their own
    (those inside a fusion are the fusion's). A device trace names an
    event ``%<instruction> = ...`` and carries no scope; this is the
    table by which a reader finds a scope's events."""
    out, inside_fusion = {}, False
    for line in hlo_text.splitlines():
        head = _HLO_HEAD.match(line)
        if head:
            inside_fusion = head.group(1).startswith("fused_computation")
            continue
        instr = _HLO_INSTR.match(line)
        if inside_fusion or not instr:
            continue
        op_name = _HLO_OP_NAME.search(line)
        if not op_name:
            continue
        innermost = next((part for part in reversed(
            op_name.group(1).split("/")) if part in scopes), None)
        if innermost is not None:
            out[instr.group(1)] = innermost
    return out


class CompileTracker:
    """Recompile observatory: wraps compiled-program callables and
    records every first-seen abstract signature as one compilation
    (see module docstring). Tracking costs one signature hash per
    launch, so engines only attach it when profiling is on."""

    def __init__(self, capacity: int = 256, clock=None, registry=None,
                 recorder=None, worker_id=None):
        self.worker_id = worker_id
        self._clock = now if clock is None else clock
        self.recorder = recorder
        self._lock = threading.Lock()
        self._log: deque = deque(maxlen=int(capacity))  # guarded-by: _lock
        self._seen: dict = {}         # guarded-by: _lock
        self._warm = False            # guarded-by: _lock
        self._n_compiles = 0          # guarded-by: _lock
        self._n_unexpected = 0        # guarded-by: _lock
        self._c_compiles = None
        if registry is not None:
            self._c_compiles = registry.counter(
                "engine_compiles_total",
                "compiled-program builds observed (first-seen "
                "abstract signatures)")
            registry.gauge(
                "engine_unexpected_compiles",
                "compilations observed AFTER the warmup watermark "
                "(SC06's invariant as a runtime alarm)",
                fn=self._unexpected)

    def _unexpected(self) -> int:
        with self._lock:
            return self._n_unexpected

    @staticmethod
    def signature(args) -> tuple:
        """Abstract signature of a call: (shape, dtype) per array
        leaf, type name for everything else — exactly what a jit
        cache keys on (weak types aside)."""
        import jax
        sig = []
        for leaf in jax.tree_util.tree_leaves(args):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                sig.append(type(leaf).__name__)
            else:
                sig.append((tuple(int(d) for d in shape),
                            str(getattr(leaf, "dtype", ""))))
        return tuple(sig)

    def wrap(self, program: str, fn, key=None):
        """Wrap ``fn`` so first-seen signatures are recorded as
        compilations. ``key`` tags the bucket the factory was built
        for (e.g. the padded window size)."""

        def wrapped(*args, **kwargs):
            sig = self.signature(args)
            t0 = self._clock()
            out = fn(*args, **kwargs)
            self.note(program, sig, self._clock() - t0, key=key)
            return out

        wrapped.__wrapped__ = fn        # the jitted program, for its text
        return wrapped

    def note(self, program: str, sig, wall_s: float, key=None) -> bool:
        """Record one observed call; True iff it was a first-seen
        signature (== one compilation; its wall time is the
        compile-proxy — the first call traces + compiles + runs)."""
        with self._lock:
            seen = self._seen.setdefault(program, set())
            if sig in seen:
                return False
            seen.add(sig)
            self._n_compiles += 1
            warm = self._warm
            if warm:
                self._n_unexpected += 1
            entry = {"program": str(program), "signature": repr(sig),
                     "bucket_key": key, "wall_s": round(wall_s, 6),
                     "post_warmup": warm}
            self._log.append(entry)
        if self._c_compiles is not None:
            self._c_compiles.inc()
        if warm:
            log_kv(_log, "unexpected_compile", level=logging.WARNING,
                   program=program, worker=self.worker_id,
                   bucket_key=key, wall_s=round(wall_s, 6))
            if self.recorder is not None:
                self.recorder.record(
                    "unexpected_compile", worker=self.worker_id,
                    program=str(program), bucket_key=key,
                    wall_s=round(wall_s, 6))
        elif self.recorder is not None:
            self.recorder.record(
                "compile", worker=self.worker_id, program=str(program),
                bucket_key=key, wall_s=round(wall_s, 6))
        return True

    def warmup_done(self) -> None:
        """Declarative watermark: every signature the workload will
        legitimately need should have compiled by now; later compiles
        are flagged unexpected."""
        with self._lock:
            self._warm = True

    def compile_log(self) -> list:
        """Bounded newest-last log of compilations (bundle component)."""
        with self._lock:
            return [dict(e) for e in self._log]

    def programs(self) -> dict:
        """program -> distinct signatures compiled."""
        with self._lock:
            return {p: len(s) for p, s in sorted(self._seen.items())}

    def stats(self) -> dict:
        with self._lock:
            return {"compiles": self._n_compiles,
                    "unexpected": self._n_unexpected,
                    "warm": self._warm}

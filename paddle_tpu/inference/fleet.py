"""Serving fleet: prefix-affinity router + worker failover (ISSUE 4
tentpole; reference shape: GSPMD's lesson that multi-worker placement
wants to be a first-class LAYER, and the Ragged Paged Attention stance
that per-engine KV state stays local — only the cheap host-side index
is shared).

A :class:`ServingFleet` owns N in-process :class:`DecodeEngine` workers
(each with its PRIVATE metrics registry and KV block pool) behind one
``submit()`` API. Three load-bearing parts:

- :class:`GlobalPrefixDirectory` — a host-side index mapping token
  prefixes (at page granularity, as incremental chain hashes over full
  blocks) to the workers whose ``PrefixCache`` holds them. Each
  worker's cache notifies the directory on publish/evict through the
  ``PrefixCache(listener=)`` hook, so the router can score workers by
  ``cached_tokens(prefix) − load_penalty(backlog, occupancy)`` and
  shared-system-prompt traffic lands where its pages already live.

  CONSISTENCY RULE: the directory is a routing HINT, never a
  correctness input. Only the owning worker's ``PrefixCache.match`` at
  admission decides what is actually reused — a stale directory entry
  costs one cold prefill, nothing more. That is why listener faults
  are swallowed and why ``drop_worker`` can be a blunt wipe.

- Failover — a worker whose :class:`EngineStallWatchdog` fires (via
  ``on_stall=``) or whose step raises is drained: its in-flight rows
  are harvested exactly like r7's lossless preemption
  (``req._resume_toks = emitted tokens``, trace marked "preempted")
  and re-routed to healthy workers, where recompute-resume admission
  replays them bit-identically to an undisturbed run (greedy decode).
  The dead engine's device state and allocator are never touched —
  harvest is host-side only.

- Metrics — per-worker registries aggregate through
  :class:`~paddle_tpu.inference.fleet_metrics.MetricsAggregator`
  (merged fleet snapshot + Prometheus exposition with ``worker="w3"``
  labels) and can be served from a stdlib scrape endpoint
  (:meth:`ServingFleet.serve_metrics`).

The fleet is driven synchronously (:meth:`step` /
:meth:`run_until_drained`) so failover tests are deterministic;
watchdog poll threads are opt-in via :meth:`start_watchdogs`.

ISSUE 9 makes the fleet SELF-HEALING instead of merely degrading:

- Restart & rejoin — :meth:`restart_worker` rebuilds a drained
  worker's engine (fresh pool/registry/watchdog) under the SAME wid;
  the prefix directory repopulates through the re-registered listener
  as the new cache publishes, and the router re-includes the worker
  after a probation warm-up. A :class:`RestartPolicy` adds automatic
  restarts with capped exponential backoff on an injected clock.
- Poison quarantine — a ``step_raised`` crash is attributed to the
  rows admitted on the crashed worker: each gets ``retry_count`` += 1
  and a ``retry`` trace mark. A request exceeding ``max_retries``
  (default 2) fails LOUDLY with :class:`RequestPoisonedError` and a
  ``poison_reason`` trace attr instead of cascading through the
  fleet; innocents co-batched with it re-route and finish
  bit-identical to a fault-free run.
- Parking — when a failover finds ZERO healthy workers, unrouteable
  requests PARK instead of raising through :meth:`step`; they
  re-route (hop reason ``restarted``) as soon as a worker rejoins.
- Degradation ladder — with an SLO engine attached, consecutive
  firing evaluations escalate a deterministic brownout: level 1
  boosts the router load penalty, level 2 disables speculative
  decode, level 3 halves the per-step token budget; everything is
  restored when the alerts resolve (``fleet_degradation_level``
  gauges it).
- Fault injection — a
  :class:`~paddle_tpu.inference.chaos.FaultInjector` installed on
  ``self.chaos`` drives all of the above from a seeded step-indexed
  schedule; ``chaos is None`` (the default) costs nothing.
"""

from __future__ import annotations

import json
import logging
import threading
from collections import deque

from ..distributed.watchdog import EngineStallWatchdog
from ..observability import MetricsRegistry, merge_snapshots
from ..observability.flight import FlightRecorder, dump_postmortem
from ..utils.log import get_logger, log_event, log_kv
from .serving import DecodeEngine, _Request, _phase, _tmark

__all__ = ["GlobalPrefixDirectory", "NoHealthyWorkersError",
           "RequestPoisonedError", "RestartPolicy", "ServingFleet"]

_log = get_logger("paddle_tpu.inference.fleet")


class NoHealthyWorkersError(RuntimeError):
    """Routing found zero healthy workers. Subclasses RuntimeError so
    pre-ISSUE-9 callers catching the bare type keep working; raised
    from :meth:`ServingFleet.submit` — internal failover paths PARK
    unrouteable requests instead of letting this escape ``step()``."""


class RequestPoisonedError(RuntimeError):
    """A request was attributed more than ``max_retries`` worker
    crashes (``step_raised`` failovers while it was admitted) and has
    been quarantined: failed loudly instead of re-routed into the next
    worker. The trace carries ``poison_reason``."""


class RestartPolicy:
    """Worker auto-restart policy: capped exponential backoff on an
    INJECTED clock (tests and the chaos bench drive a virtual clock;
    production defaults to the shared observability clock).

    A drained worker's n-th restart is scheduled ``backoff_base_s *
    2**n`` seconds (capped at ``backoff_max_s``) after the drain is
    observed; ``max_restarts`` (None = unlimited) stops a
    crash-looping worker from flapping forever. ``probation_steps``
    is how many healthy steps a rejoined worker runs before the
    router includes it again (it still drains its own backlog during
    probation). ``auto=False`` keeps the knobs (probation, backoff
    accounting for manual :meth:`ServingFleet.restart_worker` calls)
    without the automatic trigger."""

    __slots__ = ("auto", "backoff_base_s", "backoff_max_s",
                 "max_restarts", "probation_steps", "clock")

    def __init__(self, auto=True, backoff_base_s=1.0,
                 backoff_max_s=30.0, max_restarts=None,
                 probation_steps=2, clock=None):
        from ..observability.metrics import now as _now
        self.auto = bool(auto)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.max_restarts = (None if max_restarts is None
                             else int(max_restarts))
        self.probation_steps = int(probation_steps)
        self.clock = clock if clock is not None else _now

    def backoff_s(self, n_prior_restarts: int) -> float:
        return min(self.backoff_base_s * 2 ** int(n_prior_restarts),
                   self.backoff_max_s)


class _DirectoryListener:
    """Per-worker adapter bound into that worker's ``PrefixCache``."""

    __slots__ = ("_dir", "_wid")

    def __init__(self, directory, worker_id):
        self._dir = directory
        self._wid = worker_id

    def on_insert(self, tokens):
        self._dir.on_insert(self._wid, tokens)

    def on_evict(self, tokens):
        self._dir.on_evict(self._wid, tokens)


class GlobalPrefixDirectory:
    """Host-side prefix → workers index at page granularity.

    Each cached full block is recorded as an incremental CHAIN hash:
    ``h_i = hash((h_{i-1}, tokens[i*bs:(i+1)*bs]))`` with ``h_0 = 0``,
    so membership of a prefix of ``i`` full blocks is one set lookup
    per block and the directory never stores token ids. Partial
    (sub-block) leaves are not indexed — they can't be mapped shared
    at admission anyway (COW copies are private), so they carry no
    routing signal.

    Updates arrive via the per-worker :meth:`listener` objects wired
    into each ``PrefixCache``: ``insert`` adds every full-block chain
    hash of the published prefix (idempotent — sets), ``evict``
    removes the evicted node's own (deepest) chain hash; parents keep
    theirs until their own eviction cascades. See the module docstring
    for the consistency rule: this is a hint, correctness lives in the
    owning worker's cache."""

    def __init__(self, block_size: int):
        self._bs = int(block_size)
        self._by_worker: dict[str, set[int]] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def listener(self, worker_id: str) -> _DirectoryListener:
        with self._lock:
            self._by_worker.setdefault(worker_id, set())
        return _DirectoryListener(self, worker_id)

    def _chain(self, tokens):
        """Yield (depth, chain-hash) for every FULL block of tokens."""
        bs = self._bs
        h = 0
        for i in range(len(tokens) // bs):
            h = hash((h, tuple(int(t) for t in
                               tokens[i * bs:(i + 1) * bs])))
            yield i + 1, h

    def on_insert(self, worker_id: str, tokens) -> None:
        with self._lock:
            entries = self._by_worker.setdefault(worker_id, set())
            for _, h in self._chain(tokens):
                entries.add(h)

    def on_evict(self, worker_id: str, tokens) -> None:
        """``tokens`` is the root→victim path; the victim is childless,
        so only the DEEPEST chain hash leaves the index. A path ending
        in a partial leaf was never indexed — nothing to remove."""
        if not tokens or len(tokens) % self._bs:
            return
        last = None
        for _, h in self._chain(tokens):
            last = h
        with self._lock:
            self._by_worker.get(worker_id, set()).discard(last)

    def cached_tokens(self, worker_id: str, tokens) -> int:
        """Longest directory-known full-block prefix of ``tokens`` on
        ``worker_id``, in TOKENS (the router's affinity term)."""
        with self._lock:
            entries = self._by_worker.get(worker_id)
            if not entries:
                return 0
            depth = 0
            for i, h in self._chain(tokens):
                if h not in entries:
                    break
                depth = i
            return depth * self._bs

    def drop_worker(self, worker_id: str) -> None:
        """Failover wipe: a dead worker's pages are unreachable, so its
        whole index entry goes (blunt is fine — hint, not truth)."""
        with self._lock:
            self._by_worker.pop(worker_id, None)

    def stats(self) -> dict:
        with self._lock:
            return {wid: len(s) for wid, s in self._by_worker.items()}


class _Worker:
    __slots__ = ("wid", "engine", "registry", "watchdog", "pending",
                 "healthy", "fail_reason", "restarts", "restart_at",
                 "probation", "deg_saved", "legacy_snap", "role")

    def __init__(self, wid, engine, registry, watchdog):
        self.wid = wid
        self.engine = engine
        self.registry = registry
        self.watchdog = watchdog
        self.pending: list = []         # routed, not yet handed to admit
        self.role = None                # "prefill"/"decode" under an
        #                                 ISSUE 14 role split, else None
        self.healthy = True
        self.fail_reason = None
        self.restarts = 0               # completed restarts (ISSUE 9)
        self.restart_at = None          # scheduled auto-restart time
        self.probation = 0              # healthy steps before the
        #                                 router re-includes a rejoin
        self.deg_saved = None           # engine knobs saved by the
        #                                 degradation ladder
        self.legacy_snap = None         # counters/histograms folded in
        #                                 from pre-restart incarnations

    @property
    def occupancy(self) -> int:
        return sum(1 for r in self.engine._rows if r is not None)

    @property
    def load(self) -> int:
        return self.engine.backlog + self.occupancy + len(self.pending)


class ServingFleet:
    """N decode engines behind one ``submit()`` with prefix-affinity
    routing, stall/step failover, and aggregated metrics.

    ``policy`` is ``"affinity"`` (default — score each healthy worker
    by ``directory.cached_tokens(prompt) − load_penalty * load`` where
    ``load = backlog + occupancy + routed-but-unadmitted``, ties broken
    by lowest load then lowest index) or ``"round_robin"`` (the bench
    baseline). ``load_penalty`` defaults to ``block_size``: one unit of
    queued work offsets one cached page, so affinity wins only when
    reuse outweighs the imbalance it creates.

    Drive it synchronously: ``submit()`` routes immediately onto a
    per-worker pending list; each :meth:`step` runs failover for
    workers flagged unhealthy, then ``admit`` + one decode chunk on
    every healthy worker. Futures resolve as rows retire (same
    ``_Request.wait()`` contract as the engine)."""

    def __init__(self, model, n_workers=2, policy="affinity",
                 load_penalty=None, engine_kwargs=None,
                 stall_s=30.0, registry=None, qos=None,
                 max_retries=2, restart=None, tp_degree=None,
                 seq_degree=None, profile=False, flight_capacity=512,
                 postmortem_dir=None, postmortem_keep=16,
                 roles=None, migration_budget_pages=None):
        if n_workers < 1:
            raise ValueError(f"n_workers={n_workers}")
        if policy not in ("affinity", "round_robin"):
            raise ValueError(f"unknown routing policy {policy!r}")
        self.policy = policy
        # ISSUE 14: prefill/decode disaggregation. ``roles`` marks each
        # worker prefill- or decode-heavy: new prompts route to prefill
        # workers (forced chunked so long prompts stream), and a row
        # whose prompt finishes hands off — block tables, published
        # pages and all — to a decode worker over the KV transplant
        # path (migration.py). ``migration_budget_pages`` separately
        # bounds warm-prefix migration on ROUTE: when an affinity
        # directory hit loses to its own load penalty, the chain moves
        # to the routed winner instead of re-prefilling cold, up to
        # this many pages per fleet step. Both default OFF — r14
        # routing/failover behavior and outputs stay bit-identical.
        self.roles = tuple(roles) if roles is not None else None
        if self.roles is not None:
            if len(self.roles) != n_workers:
                raise ValueError(
                    f"roles has {len(self.roles)} entries for "
                    f"n_workers={n_workers}")
            bad = [r for r in self.roles
                   if r not in ("prefill", "decode")]
            if bad:
                raise ValueError(f"unknown roles {bad!r} (want "
                                 f"'prefill' or 'decode')")
            if ("prefill" not in self.roles
                    or "decode" not in self.roles):
                raise ValueError(
                    "a role split needs at least one prefill AND one "
                    "decode worker")
        self.migration_budget_pages = (int(migration_budget_pages)
                                       if migration_budget_pages
                                       else 0)
        self._mig_left = self.migration_budget_pages  # guarded-by: _lock
        #                                 per-step transplant budget;
        #                                 _step_inner refills it
        kw = dict(engine_kwargs or {})
        kw.setdefault("paged", True)
        kw.pop("qos", None)     # the fleet owns the shared QoS policy
        # ISSUE 10: scale-out x scale-up. tp_degree builds every worker
        # as a SHARDED engine over its own disjoint submesh (worker i
        # gets devices [i*tp, (i+1)*tp)), so routing, failover, restart
        # and chaos compose with tensor parallelism unchanged. The
        # submesh is derived from the worker id in _build_worker, NOT
        # stored in _engine_kw — a restarted worker rebuilds the SAME
        # submesh.
        kw.pop("mesh", None)    # per-worker submeshes only
        self.tp_degree = int(tp_degree) if tp_degree else None
        # ISSUE 16: seq_degree adds the second mesh axis per worker —
        # worker i's submesh becomes the 2-D (seq, tp) grid over
        # devices [i*tp*seq, (i+1)*tp*seq). Normalized so seq_degree=1
        # is byte-identical to the 1-D fleet.
        sq = int(seq_degree) if seq_degree else 1
        self.seq_degree = sq if sq > 1 else None
        if self.tp_degree is not None or self.seq_degree is not None:
            import jax
            n_dev = len(jax.devices())
            per = (self.tp_degree or 1) * (self.seq_degree or 1)
            if self.seq_degree is None:
                if n_workers * per > n_dev:
                    raise ValueError(
                        f"n_workers={n_workers} x tp_degree="
                        f"{self.tp_degree} exceeds {n_dev} devices")
            elif n_workers * per > n_dev:
                raise ValueError(
                    f"n_workers={n_workers} x tp_degree="
                    f"{self.tp_degree or 1} x seq_degree="
                    f"{self.seq_degree} exceeds {n_dev} devices")
        # ISSUE 6: one QoSPolicy shared by the router (token-bucket
        # admission at submit, shed planning) and every worker engine
        # (fair-share scheduling weights). The fleet's gate is the only
        # admission check — engine gates stay empty because requests
        # enter workers via routed pending lists, not engine.submit().
        self.qos = qos
        self._qos_gate = qos.gate() if qos is not None else None
        self._shed = False
        self._shed_target = 0
        block_size = int(kw.get("block_size", 16))
        self.load_penalty = (float(load_penalty)
                             if load_penalty is not None
                             else float(block_size))
        self.directory = GlobalPrefixDirectory(block_size)
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self._c_submitted = self.metrics.counter(
            "fleet_submitted_total", "requests accepted by the router")
        self._c_affinity_hits = self.metrics.counter(
            "fleet_affinity_hits_total",
            "submissions routed to a worker with a cached prefix")
        self._c_failovers = self.metrics.counter(
            "fleet_failovers_total", "workers drained after stall/fault")
        self._c_rerouted = self.metrics.counter(
            "fleet_rerouted_total",
            "requests re-routed off a failed worker")
        self._c_shed = self.metrics.counter(
            "fleet_shed_total",
            "pending requests shed while an SLO alert fired")
        self._c_qos_rejected = self.metrics.counter(
            "fleet_qos_rejected_total",
            "requests rejected by tenant admission")
        # ISSUE 9: self-healing accounting
        self._c_restarts = self.metrics.counter(
            "fleet_restarts_total",
            "drained workers rebuilt and rejoined")
        self._c_poisoned = self.metrics.counter(
            "fleet_poisoned_total",
            "requests quarantined after max_retries crash attributions")
        # ISSUE 14: disaggregation accounting
        self._c_migrations = self.metrics.counter(
            "fleet_migrations_total",
            "cross-worker KV chain transplants completed")
        self._c_migrated_pages = self.metrics.counter(
            "fleet_kv_migrated_pages_total",
            "KV pages moved between worker pools")
        self._c_stale_hints = self.metrics.counter(
            "fleet_prefix_stale_hints_total",
            "directory hits refuted by the owning cache at transplant "
            "time (the hint-only consistency rule observed in action)")
        self.metrics.gauge(
            "fleet_healthy_workers", "workers currently routable",
            fn=lambda: sum(1 for w in self.workers if w.healthy))
        self.metrics.gauge(
            "fleet_degradation_level",
            "brownout ladder level (0=normal, 1=penalty boost, "
            "2=+spec off, 3=+step budget halved)",
            fn=lambda: self._degradation)
        # restart_worker rebuilds engines with EXACTLY the ctor args
        # the fleet was born with — keep them
        self.model = model
        self._engine_kw = kw
        self._stall_s = stall_s
        self.max_retries = int(max_retries)
        self.restart = restart          # RestartPolicy or None
        self._parked: list = []         # guarded-by: _lock
        #                                 unrouteable during failover;
        #                                 re-route on rejoin, never
        #                                 raise through step()
        self.chaos = None               # FaultInjector.install() hook
        self._degradation = 0
        self._deg_boost = 1.0           # set by enable_slo
        # ISSUE 13: flight recorder + postmortem surface. The fleet
        # ring is ALWAYS on — the r9-r14 failure machinery (failover,
        # restart, poison, shed, injected faults) is worthless to
        # debug without the events leading up to it — and per-worker
        # rings mirror into it with a ``src`` tag. profile=True
        # additionally threads a StepProfiler + CompileTracker into
        # every worker engine and a router-side profiler for the
        # schedule/telemetry phases; postmortem_dir arms automatic
        # bundle dumps on stall, restart harvest, and poison
        # quarantine.
        self.profile = bool(profile)
        self.postmortem_dir = postmortem_dir
        self.postmortem_keep = int(postmortem_keep)
        self.flight = FlightRecorder(capacity=int(flight_capacity),
                                     name="fleet",
                                     registry=self.metrics)
        self._prof = None
        if self.profile:
            from ..observability.profiling import StepProfiler
            self._prof = StepProfiler(registry=self.metrics,
                                      recorder=self.flight,
                                      worker_id="router")
        self.workers: list[_Worker] = []
        for i in range(n_workers):
            wid = f"w{i}"
            eng, reg, wd = self._build_worker(wid)
            w = _Worker(wid, eng, reg, wd)
            if self.roles is not None:
                w.role = self.roles[i]
            self.workers.append(w)
        self._rr = 0                    # round-robin cursor
        self._seq = 0                   # fleet-wide FCFS stamp: keeps
        #                                 _sched_seq unique across the
        #                                 per-worker schedulers, so a
        #                                 re-routed request never
        #                                 collides (or loses its global
        #                                 arrival order) on the new
        #                                 worker's heap
        self._lock = threading.Lock()
        self._http = None
        # ISSUE 5: trace retention for cross-worker Chrome export +
        # shipper payloads. Bounded so a long-lived fleet never grows.
        self._traces: deque = deque(maxlen=1024)  # every trace seen
        self._open_traces: list = []              # not yet terminal
        self._retired_unshipped: list = []        # summaries to ship
        self._base_load_penalty = self.load_penalty
        self.slo = None
        self.shipper = None
        self.metrics.gauge(
            "fleet_load_penalty",
            "current router load penalty (SLO alerts raise it)",
            fn=lambda: self.load_penalty)

    def _build_worker(self, wid):
        """One worker's engine + private registry + watchdog. Used at
        construction AND by :meth:`restart_worker` — a rebuilt worker
        is indistinguishable from a fresh one (fresh pool, fresh
        registry, fresh watchdog, listener re-registered so the prefix
        directory repopulates as the new cache publishes)."""
        reg = MetricsRegistry()
        kw = dict(self._engine_kw)
        if self.roles is not None \
                and self.roles[int(wid[1:])] == "prefill":
            # prefill-heavy worker: always chunked, so long prompts
            # stream through the step budget and finished rows hand
            # off to a decode worker at page boundaries (ISSUE 14).
            # Restart rebuilds derive the same role from the wid.
            kw["chunked_prefill"] = True
        if self.seq_degree is not None:
            # ISSUE 16: 2-D (seq, tp) submesh per worker. Derived from
            # the wid like the 1-D path, so a restarted worker rebuilds
            # the SAME 2-D submesh.
            import jax
            from .sharding import make_mesh
            i = int(wid[1:])
            per = (self.tp_degree or 1) * self.seq_degree
            kw["mesh"] = make_mesh(
                self.tp_degree or 1, self.seq_degree,
                devices=jax.devices()[i * per:(i + 1) * per])
        elif self.tp_degree is not None:
            import jax
            from .sharding import make_tp_mesh
            i = int(wid[1:])
            kw["mesh"] = make_tp_mesh(
                self.tp_degree,
                devices=jax.devices()[i * self.tp_degree:
                                      (i + 1) * self.tp_degree])
        rec = FlightRecorder(capacity=self.flight.capacity, name=wid,
                             forward_to=self.flight, registry=reg)
        eng = DecodeEngine(
            self.model, registry=reg, worker_id=wid,
            prefix_listener=self.directory.listener(wid),
            qos=self.qos, profile=self.profile or None,
            recorder=rec, **kw)
        wd = EngineStallWatchdog(
            reg, stall_s=self._stall_s, recorder=rec,
            on_stall=lambda info, w=wid: self._on_stall(w, info))
        return eng, reg, wd

    def _on_stall(self, wid, info):
        """Watchdog hook: flag the worker AND freeze the evidence —
        the bundle written here is the state at detection, before the
        next step's failover mutates it."""
        flagged = self._mark_unhealthy(wid, "stall", info)
        if flagged:
            self.dump_postmortem(f"stall:{wid}")
        return flagged

    # -- routing ------------------------------------------------------------
    def _healthy(self) -> list[_Worker]:
        return [w for w in self.workers if w.healthy]

    def _route(self, ids) -> _Worker:
        """Pick the worker for a prompt. MUST be called with the lock
        held. Raises when no healthy worker remains. The routing
        decision (reason + scored candidates) is kept on
        ``self._last_route`` so callers can stamp it onto the request
        trace (ISSUE 5 router span)."""
        all_healthy = self._healthy()
        if not all_healthy:
            raise NoHealthyWorkersError(
                "ServingFleet has no healthy workers")
        # probation (ISSUE 9): a freshly-rejoined worker drains its own
        # work for a warm-up window before the router includes it again
        # — unless it is all that's left
        healthy = [w for w in all_healthy if not w.probation] \
            or all_healthy
        if self.roles is not None:
            # ISSUE 14 role split: new prompts go to prefill workers
            # (decode workers receive their rows via handoff). With
            # every prefill worker down, any healthy worker serves
            # end-to-end — a degraded fleet beats a dead one.
            healthy = [w for w in healthy if w.role == "prefill"] \
                or healthy
        if self.policy == "round_robin" or len(healthy) == 1:
            w = healthy[self._rr % len(healthy)]
            self._rr += 1
            self._last_route = {
                "reason": ("single_healthy" if len(healthy) == 1
                           and self.policy != "round_robin"
                           else "round_robin"),
                "candidates": [{"worker": x.wid, "load": x.load}
                               for x in healthy]}
            return w
        scored = []
        for w in healthy:
            cached = self.directory.cached_tokens(w.wid, ids)
            load = w.load
            score = cached - self.load_penalty * load
            scored.append((-score, load, w.wid, w, cached))
        scored.sort(key=lambda t: t[:3])
        w, cached = scored[0][3], scored[0][4]
        if cached > 0:
            self._c_affinity_hits.inc()
        self._last_route = {
            "reason": "affinity_hit" if cached > 0 else "least_loaded",
            "candidates": [{"worker": s[2], "score": -s[0],
                            "load": s[1], "cached_tokens": s[4]}
                           for s in scored]}
        return w

    def _stamp_route(self, req, w: _Worker) -> None:
        """Router span onto the request's trace: chosen worker, why,
        and every candidate's score (lock held — reads _last_route)."""
        tr = getattr(req, "trace", None)
        if tr is None:
            return
        info = getattr(self, "_last_route", None) or {}
        tr.set_attr("worker_id", w.wid)
        tr.set_attr("route_reason", info.get("reason", self.policy))
        tr.set_attr("route_candidates", info.get("candidates", []))
        tr.mark("routed", worker=w.wid)

    def _maybe_migrate_locked(self, ids, winner: _Worker) -> None:
        """Warm-prefix migration on route (ISSUE 14): the affinity
        score just sent this prompt to ``winner``, but a LOSING
        candidate held strictly more cached prefix — a directory hit
        beaten by its own load penalty. Move that chain to the winner
        (bounded by the per-step page budget) so the routed worker
        prefills warm instead of cold. Every failure mode — stale
        hint, full destination pool, injected ``migration_fail``,
        anything raising — degrades to exactly the cold prefill that
        would have happened anyway. Lock held by caller."""
        if (self._mig_left <= 0 or self.policy != "affinity"):
            return
        info = getattr(self, "_last_route", None) or {}
        cands = info.get("candidates") or []
        win_cached, best = 0, None
        for c in cands:
            ct = int(c.get("cached_tokens", 0) or 0)
            if c.get("worker") == winner.wid:
                win_cached = ct
            elif best is None or ct > best[0]:
                best = (ct, c["worker"])
        if best is None or best[0] <= win_cached:
            return
        src = next((w for w in self.workers
                    if w.wid == best[1] and w.healthy), None)
        if src is None:
            return
        try:
            if self.chaos is not None:
                self.chaos.check_migration(src.wid, winner.wid)
            from .migration import transplant_prefix
            res = transplant_prefix(src.engine, winner.engine, ids,
                                    max_pages=self._mig_left)
        except Exception as e:  # noqa: BLE001 — a dead transplant
            # costs one cold prefill, never the request (the chaos
            # migration_fail fault lands here by design)
            log_kv(_log, "kv_migration_failed", level=logging.WARNING,
                   src=best[1], dst=winner.wid,
                   error=type(e).__name__, detail=str(e))
            self.flight.record("kv_migration_failed", src=best[1],
                               dst=winner.wid,
                               error=type(e).__name__)
            return
        if res.reason == "stale":
            # the directory promised a chain the owner no longer holds
            # (evicted since the last on_insert) — hint, not truth
            self._c_stale_hints.inc()
            return
        if not res.moved:
            return
        self._mig_left -= res.pages
        self._c_migrations.inc()
        self._c_migrated_pages.inc(res.pages)
        # the moved tokens charge the winner's NEXT step budget: KV
        # bandwidth spent on its behalf is still its pacing debt
        winner.engine._mig_debt += res.tokens
        self.flight.record("kv_migrated", src=src.wid,
                           dst=winner.wid, pages=res.pages,
                           tokens=res.tokens, fused=res.fused)
        log_kv(_log, "kv_migrated", level=logging.DEBUG, src=src.wid,
               dst=winner.wid, pages=res.pages, tokens=res.tokens)

    def submit(self, input_ids, max_new_tokens=32,
               priority=0, tenant=None) -> _Request:
        """Route one request and return its future (``req.wait()``
        resolves once some worker retires it — drive :meth:`step` or
        :meth:`run_until_drained` to make progress).

        With a ``qos=`` policy (ISSUE 6), ``tenant`` selects the
        request's token bucket / fair-share queue / shed tier. An
        over-rate request is held behind its bucket (released and
        routed by a later :meth:`step`) or, for ``on_limit="reject"``
        tenants, failed immediately with the rejection reason on the
        trace — ``req.wait()`` raises either way."""
        import numpy as _np
        ids = _np.asarray(input_ids).reshape(-1)
        req = _Request(input_ids, max_new_tokens, priority=priority,
                       tenant=tenant)
        with self._lock:
            req._sched_seq = self._seq
            self._seq += 1
            self._c_submitted.inc()
            self._traces.append(req.trace)
            self._open_traces.append(req.trace)
            if self._qos_gate is not None:
                verdict, reason = self._qos_gate.decide(req)
                if verdict == "reject":
                    self._c_qos_rejected.inc()
                    req.trace.set_attr("reject_reason", reason)
                    req.error = PermissionError(
                        f"QoS rejected ({reason}) for tenant "
                        f"{tenant!r}")
                    req.event.set()
                    _tmark(req, "failed")
                    log_kv(_log, "qos_rejected", level=logging.WARNING,
                           req=req.trace.request_id, tenant=tenant,
                           reason=reason)
                    return req
                if verdict == "throttle":
                    # gate wait opens the queued->admitted stint
                    _tmark(req, "queued")
                    log_kv(_log, "qos_throttled", level=logging.DEBUG,
                           req=req.trace.request_id, tenant=tenant)
                    return req
            w = self._route(ids)
            self._maybe_migrate_locked(ids, w)
            self._stamp_route(req, w)
            w.pending.append(req)
        log_kv(_log, "routed", level=logging.DEBUG, worker=w.wid,
               req=req.trace.request_id, tokens=int(ids.size),
               policy=self.policy)
        return req

    # -- health / failover --------------------------------------------------
    def _mark_unhealthy(self, wid, reason, info=None):
        """Flag only — safe from watchdog threads; the harvest itself
        runs inside :meth:`step` on the driving thread."""
        for w in self.workers:
            if w.wid == wid and w.healthy:
                w.healthy = False
                w.fail_reason = reason
                log_kv(_log, "worker_unhealthy", level=logging.ERROR,
                       worker=wid, reason=reason)
                log_event("fleet_worker_unhealthy", worker=wid,
                          reason=reason)
                self.flight.record("worker_unhealthy", worker=wid,
                                   reason=reason)
                return True
        return False

    def kill_worker(self, wid, reason="killed") -> int:
        """Test/bench hook: immediately drain ``wid`` and re-route its
        work. Returns the number of requests re-routed."""
        with self._lock:
            if not self._mark_unhealthy(wid, reason):
                return 0
            return self._failover_locked()

    def _harvest(self, w: _Worker, blame: bool = False) -> list:
        """Host-side drain of a dead worker: in-flight rows become
        recompute-resume requests exactly like r7 preemption (emitted
        tokens snapshotted, trace marked), scheduler backlog and the
        unadmitted pending list ride along untouched. The engine's
        device arrays/allocator are NOT touched — the worker is dead,
        its pages are unreachable, and correctness only needs the host
        tokens.

        ``blame=True`` (a ``step_raised`` crash, ISSUE 9) attributes
        the crash to exactly the rows ADMITTED at crash time: each
        gets ``retry_count`` += 1 and a ``retry`` trace mark. Backlog
        and pending requests were not running — they stay innocent."""
        eng = w.engine
        out = []
        for slot, row in enumerate(eng._rows):
            if row is None:
                continue
            req = row["req"]
            # a row still mid-chunked-prefill (ISSUE 7) has toks == []
            # but may carry resume tokens from an earlier preemption —
            # those, not the empty decode list, are what survives
            if "pf_seq" in row:
                req._resume_toks = list(row.get("pf_resume") or [])
            else:
                req._resume_toks = list(row["toks"])
            _tmark(req, "preempted")
            if blame:
                req.retry_count = getattr(req, "retry_count", 0) + 1
                tr = getattr(req, "trace", None)
                if tr is not None:
                    tr.set_attr("retry_count", req.retry_count)
                _tmark(req, "retry", worker=w.wid)
            eng._rows[slot] = None
            out.append(req)
        out.extend(eng.drain_pending())
        out.extend(w.pending)
        w.pending = []
        # resumed requests must come back before never-started ones of
        # equal priority — the fleet-wide _sched_seq already encodes
        # that; sort keeps the re-route deterministic regardless of
        # slot order
        out.sort(key=lambda r: (-int(getattr(r, "priority", 0) or 0),
                                r._sched_seq))
        return out

    def _failover_locked(self) -> int:
        """Drain every worker flagged unhealthy; re-route its requests.
        Lock held by caller."""
        moved = 0
        for w in self.workers:
            if w.healthy or w.fail_reason == "drained":
                continue
            reason = w.fail_reason or "failover"
            # ISSUE 9: only a raising STEP blames its admitted rows —
            # a stall/hang says nothing about which request is poison
            blame = reason.startswith("step_raised")
            reqs = self._harvest(w, blame=blame)
            self.directory.drop_worker(w.wid)
            self._c_failovers.inc()
            w.fail_reason = "drained"
            parked = 0
            for req in reqs:
                if getattr(req, "retry_count", 0) > self.max_retries:
                    self._poison_request(req, reason, w.wid)
                    continue
                try:
                    target = self._route(req.ids.reshape(-1))
                except NoHealthyWorkersError:
                    # nowhere to go mid-failover: PARK, never raise
                    # through step() — a rejoining worker unparks
                    self._park_locked(req, w.wid)
                    parked += 1
                    continue
                tr = getattr(req, "trace", None)
                if tr is not None:
                    # ONE trace tells the whole story: the harvested
                    # trace carries a hop linking the dead worker's
                    # segment to the re-routed one (ISSUE 5)
                    tr.add_hop(w.wid, target.wid, reason=reason)
                    self._stamp_route(req, target)
                target.pending.append(req)
                self._c_rerouted.inc()
                moved += 1
            log_kv(_log, "failover", level=logging.ERROR,
                   worker=w.wid, rerouted=len(reqs) - parked,
                   parked=parked)
            log_event("fleet_failover", worker=w.wid,
                      rerouted=len(reqs))
            self.flight.record("failover", worker=w.wid,
                               reason=reason,
                               rerouted=len(reqs) - parked,
                               parked=parked)
            # ISSUE 13: one bundle per drained worker — the flight ring
            # at this point holds the fault/stall event next to the
            # failover it provoked (dump_postmortem never takes the
            # fleet lock, so calling it here under _lock is safe)
            self.dump_postmortem(f"failover:{w.wid}:{reason}")
        return moved

    def _poison_request(self, req, reason: str, wid: str) -> None:
        """Quarantine (ISSUE 9): the request rode more than
        ``max_retries`` crashing workers — fail it loudly instead of
        feeding it to the next one. The trace keeps the whole story:
        ``retry`` marks per attribution, ``quarantined`` +
        ``poison_reason`` here, then the terminal ``failed``."""
        tr = getattr(req, "trace", None)
        n = getattr(req, "retry_count", 0)
        poison_reason = (f"{reason} on {wid}: {n} crash attributions "
                         f"exceed max_retries={self.max_retries}")
        if tr is not None:
            tr.set_attr("poison_reason", poison_reason)
            tr.mark("quarantined", worker=wid)
        req.error = RequestPoisonedError(
            f"request quarantined as poison ({poison_reason}); "
            f"workers it crashed: "
            f"{tr.workers if tr is not None else '?'}")
        req.event.set()
        _tmark(req, "failed")
        self._c_poisoned.inc()
        log_kv(_log, "request_poisoned", level=logging.ERROR,
               worker=wid, retries=n,
               req=tr.request_id if tr is not None else None,
               reason=poison_reason)
        log_event("fleet_request_poisoned", worker=wid, retries=n)
        self.flight.record(
            "poisoned", worker=wid, retries=n,
            req=tr.request_id if tr is not None else None)
        self.dump_postmortem(f"poison:{wid}")

    def _park_locked(self, req, frm) -> None:
        req._parked_from = frm
        tr = getattr(req, "trace", None)
        if tr is not None:
            tr.set_attr("parked", True)
        self._parked.append(req)
        log_kv(_log, "request_parked", level=logging.WARNING,
               frm=frm,
               req=tr.request_id if tr is not None else None)

    def _unpark_locked(self) -> int:
        """Re-route parked requests once a healthy worker exists (a
        rejoin, or late discovery that one survived). The hop reason
        is ``restarted`` — the trace shows the request waited out the
        outage."""
        if not self._parked or not self._healthy():
            return 0
        parked, self._parked = self._parked, []
        moved = 0
        for req in sorted(parked, key=lambda r: (
                -int(getattr(r, "priority", 0) or 0), r._sched_seq)):
            target = self._route(req.ids.reshape(-1))
            tr = getattr(req, "trace", None)
            if tr is not None:
                tr.add_hop(getattr(req, "_parked_from", None),
                           target.wid, reason="restarted")
                tr.set_attr("parked", False)
                self._stamp_route(req, target)
            target.pending.append(req)
            self._c_rerouted.inc()
            moved += 1
        if moved:
            log_kv(_log, "unparked", level=logging.WARNING,
                   count=moved)
        return moved

    # -- restart & rejoin (ISSUE 9) -----------------------------------------
    def restart_worker(self, wid: str) -> int:
        """Rebuild a drained worker in place and rejoin it: fresh
        engine/pool/registry/watchdog under the same wid, listener
        re-registered (the prefix directory repopulates as the new
        cache publishes), probation warm-up before the router includes
        it. Returns the worker's completed restart count."""
        with self._lock:
            return self._restart_worker_locked(wid)

    def _restart_worker_locked(self, wid: str) -> int:
        w = next((x for x in self.workers if x.wid == wid), None)
        if w is None:
            raise ValueError(f"unknown worker {wid!r}")
        if w.healthy:
            raise RuntimeError(
                f"worker {wid} is healthy — nothing to restart")
        if w.fail_reason != "drained":
            self._failover_locked()     # harvest leftovers first
        was_polling = w.watchdog.running
        w.watchdog.stop()
        # counter continuity (ISSUE 9): the dead incarnation's counters
        # and histograms stay part of the fleet story — only its gauges
        # die with it (a dead engine's point-in-time state must not sum
        # into the live fleet's). Per-worker Prometheus output still
        # shows the reset; rate() consumers handle that natively.
        final = w.registry.snapshot()
        final.pop("gauges", None)
        w.legacy_snap = (final if w.legacy_snap is None
                         else merge_snapshots([w.legacy_snap, final]))
        eng, reg, wd = self._build_worker(wid)
        w.engine, w.registry, w.watchdog = eng, reg, wd
        if was_polling:
            w.watchdog.start()
        w.pending = []
        w.healthy = True
        w.fail_reason = None
        w.restarts += 1
        w.restart_at = None
        w.probation = (self.restart.probation_steps
                       if self.restart is not None else 2)
        w.deg_saved = None
        self._apply_degradation_worker(w)
        self._c_restarts.inc()
        log_kv(_log, "worker_restarted", level=logging.WARNING,
               worker=wid, restarts=w.restarts,
               probation=w.probation)
        log_event("fleet_worker_restarted", worker=wid,
                  restarts=w.restarts)
        self.flight.record("worker_restarted", worker=wid,
                           restarts=w.restarts,
                           probation=w.probation)
        self.dump_postmortem(f"restart:{wid}")
        self._unpark_locked()
        return w.restarts

    def _auto_restart_locked(self) -> int:
        """Advance the restart policy's injected clock: schedule a
        backoff for freshly-drained workers, restart those whose
        backoff elapsed. Runs every step; no policy = no-op."""
        if self.restart is None or not self.restart.auto:
            return 0
        t = self.restart.clock()
        n = 0
        for w in self.workers:
            if w.healthy or w.fail_reason != "drained":
                continue
            if w.restart_at is None:
                if (self.restart.max_restarts is not None
                        and w.restarts >= self.restart.max_restarts):
                    continue            # flapping cap: stays dead
                w.restart_at = t + self.restart.backoff_s(w.restarts)
                log_kv(_log, "restart_scheduled",
                       level=logging.WARNING, worker=w.wid,
                       at=w.restart_at, prior_restarts=w.restarts)
            elif t >= w.restart_at:
                self._restart_worker_locked(w.wid)
                n += 1
        return n

    # -- SLO-driven load shedding (ISSUE 6) ---------------------------------
    def _shed_locked(self) -> int:
        """Shed pending work down to the configured target while a
        burn-rate alert fires. Candidates are everything not yet
        decoding (gate-held, routed, and scheduler-queued requests);
        the QoS planner picks victims lowest-tier-first, newest-first,
        never cutting a tenant below its ``shed_floor`` of retained
        pending+running requests. Victims fail LOUDLY — error set,
        ``shed_reason`` on the trace, per-tenant ``qos_shed_total``
        increment. Lock held by caller."""
        from .qos import tenant_of
        cand = []
        running: dict = {}
        if self._qos_gate is not None:
            cand.extend(self._qos_gate.held())
        for w in self.workers:
            if not w.healthy:
                continue
            cand.extend(w.pending)
            sch = w.engine._sched
            if sch is not None:
                cand.extend(sch.requests())
            for row in w.engine._rows:
                if row is not None:
                    t = tenant_of(row["req"])
                    running[t] = running.get(t, 0) + 1
        victims = self.qos.shed_plan(cand, running,
                                     target=self._shed_target)
        if not victims:
            return 0
        firing = sorted(n for n, s in self.slo.states().items()
                        if s == "firing")
        reason = "slo_burn_rate:" + ",".join(firing)
        if self._qos_gate is not None:
            self._qos_gate.remove(victims)
        vids = {id(r) for r in victims}
        for w in self.workers:
            if not w.healthy:
                continue
            w.pending = [r for r in w.pending if id(r) not in vids]
            sch = w.engine._sched
            if sch is not None:
                sch.remove(victims)
        for req in victims:
            self._shed_request(req, reason)
        log_kv(_log, "shed", level=logging.WARNING,
               count=len(victims), reason=reason,
               remaining=self.pending_work())
        log_event("fleet_shed", count=len(victims), reason=reason)
        self.flight.record("shed", count=len(victims), reason=reason)
        return len(victims)

    def _shed_request(self, req, reason: str) -> None:
        from .qos import RequestShedError, tenant_of
        tenant = tenant_of(req)
        tr = getattr(req, "trace", None)
        if tr is not None:
            tr.set_attr("shed_reason", reason)
        req.error = RequestShedError(
            f"shed under SLO pressure ({reason}, tenant={tenant!r})")
        req.event.set()
        _tmark(req, "failed")
        self.qos.note_shed(tenant)
        self._c_shed.inc()

    # -- driving ------------------------------------------------------------
    def step(self) -> int:
        """One synchronous fleet step: failover anything flagged
        unhealthy, then admit + one decode chunk per healthy worker (a
        raising step fails the WORKER, not the fleet — its requests
        re-route on the spot). Returns live rows across the fleet."""
        prof = self._prof
        if prof is None:
            return self._step_inner()
        prof.begin_step()
        try:
            return self._step_inner()
        finally:
            prof.end_step()

    def _step_inner(self) -> int:
        if self.chaos is not None:
            # deterministic fault injection (ISSUE 9): advance the
            # step-indexed schedule before anything else observes it
            self.chaos.begin_step(self)
        with _phase(self._prof, "schedule"), self._lock:
            # refill the per-step transplant budget (ISSUE 14)
            self._mig_left = self.migration_budget_pages
            if self._qos_gate is not None:
                # buckets refilled since submit: route the released
                # requests in arrival order before this step's admission
                for req in self._qos_gate.release():
                    try:
                        w = self._route(req.ids.reshape(-1))
                    except NoHealthyWorkersError:
                        self._park_locked(req, None)
                        continue
                    self._stamp_route(req, w)
                    w.pending.append(req)
            self._failover_locked()
            self._auto_restart_locked()
            self._unpark_locked()
            if (self._shed and self.slo is not None
                    and self.slo.firing()):
                self._shed_locked()
        alive = 0
        for w in self.workers:
            if not w.healthy:
                continue
            eng = w.engine
            try:
                if self.chaos is not None:
                    if self.chaos.suppress_step(w):
                        # injected hang: heartbeat frozen, rows stuck —
                        # the watchdog path is how this gets noticed
                        alive += w.occupancy
                        continue
                    self.chaos.before_worker_step(w)
                with self._lock:
                    batch, w.pending = w.pending, []
                # run admission even with nothing newly routed: freed
                # slots re-admit the engine's own scheduler backlog
                eng.admit(batch)
                if batch:               # contiguous-mode engines may
                    with self._lock:    # leave a tail unconsumed
                        w.pending = batch + w.pending
                if not eng.idle():
                    eng.decode_once()
            except Exception as e:  # noqa: BLE001 — worker fault =>
                with self._lock:    # failover, not fleet crash
                    self._mark_unhealthy(
                        w.wid, f"step_raised:{type(e).__name__}")
                    self._failover_locked()
                continue
            if w.probation:
                # a healthy step served: burn down the rejoin warm-up
                w.probation -= 1
            alive += w.occupancy
        if self.roles is not None:
            # ISSUE 14: rows whose prompts just finished on a prefill
            # worker hand off to decode workers before the next step
            with _phase(self._prof, "schedule"), self._lock:
                self._handoff_prefilled_locked()
        if self.shipper is not None:
            # periodic off-host flush rides the step loop; tick() is
            # O(1) between intervals and contains every sink fault, so
            # the serving path is unaffected (bit-identical outputs —
            # tested)
            with _phase(self._prof, "telemetry"):
                self.shipper.tick()
        return alive

    def _handoff_prefilled_locked(self) -> None:
        """Role-split handoff (ISSUE 14): every row on a prefill
        worker whose prompt has finished (no mid-prefill state left)
        moves to the least-loaded healthy decode worker — published
        pages ride the KV transplant, the request re-queues as a
        recompute-resume (the r7 preemption contract, so outputs stay
        bit-identical), and the trace gains a ``migrated`` hop. Any
        failure — injected ``migration_fail``, full decode pool —
        leaves the row decoding where it is: correct, just not
        disaggregated. Lock held by caller."""
        decode = [w for w in self.workers
                  if w.healthy and w.role == "decode"]
        if not decode:
            return
        for w in self.workers:
            if not w.healthy or w.role != "prefill":
                continue
            if w.engine._cache is None:
                continue        # no radix path — nothing to transplant
            for slot, row in enumerate(list(w.engine._rows)):
                if row is None or "pf_seq" in row:
                    continue
                if len(row["toks"]) >= row["req"].max_new:
                    continue    # retiring on its own this step
                dst = min(decode, key=lambda d: (d.load, d.wid))
                self._handoff_row_locked(w, dst, slot)

    def _handoff_row_locked(self, src_w: _Worker, dst_w: _Worker,
                            slot: int) -> bool:
        src = src_w.engine
        row = src._rows[slot]
        req = row["req"]
        valid = int(src._lens[slot])
        bs = src.block_size
        full = (valid // bs) * bs
        if full <= 0:
            return False        # under one page: cheaper to keep
        try:
            if self.chaos is not None:
                self.chaos.check_migration(src_w.wid, dst_w.wid)
            seq = src._cached_seq(row)[:valid]
            # publish the finished prompt's full pages (idempotent —
            # retire would publish the same chain), then transplant
            src._cache.insert(seq[:full], row["pages"][:full // bs])
            from .migration import transplant_prefix
            res = transplant_prefix(src, dst_w.engine, seq[:full])
        except Exception as e:  # noqa: BLE001 — a failed handoff
            # keeps the row decoding on the prefill worker
            log_kv(_log, "kv_handoff_failed", level=logging.WARNING,
                   src=src_w.wid, dst=dst_w.wid,
                   error=type(e).__name__, detail=str(e))
            self.flight.record("kv_migration_failed", src=src_w.wid,
                               dst=dst_w.wid, error=type(e).__name__)
            return False
        if not res.moved:
            return False
        # requeue exactly like a preemption harvest: emitted tokens
        # snapshot to resume, row state released on the source
        req._resume_toks = list(row["toks"])
        src._release_row_pages(row)
        src._tables[slot] = 0
        src._lens[slot] = 0
        src._rows[slot] = None
        tr = getattr(req, "trace", None)
        if tr is not None:
            tr.add_hop(src_w.wid, dst_w.wid, reason="migrated")
        dst_w.pending.append(req)
        dst_w.engine._mig_debt += res.tokens
        self._c_migrations.inc()
        self._c_migrated_pages.inc(res.pages)
        self.flight.record("kv_migrated", src=src_w.wid,
                           dst=dst_w.wid, pages=res.pages,
                           tokens=res.tokens, fused=res.fused,
                           handoff=True)
        log_kv(_log, "kv_handoff", level=logging.DEBUG,
               src=src_w.wid, dst=dst_w.wid, pages=res.pages,
               req=tr.request_id if tr is not None else None)
        return True

    def pending_work(self) -> int:
        """Requests anywhere in flight: routed, scheduled, running, or
        held behind a tenant's token bucket (those drain only as the
        bucket's clock advances)."""
        gated = self._qos_gate.depth() if self._qos_gate is not None \
            else 0
        # len() is a single atomic read and _parked only mutates on
        # the step thread; pending_work runs both with the fleet lock
        # held (_shed_locked) and without (run_until_drained), so it
        # cannot take the non-reentrant lock itself.
        parked = len(self._parked)  # staticcheck: disable=SC05
        return sum(w.load for w in self.workers if w.healthy) \
            + sum(len(w.pending) for w in self.workers
                  if not w.healthy) \
            + parked \
            + gated

    def _stuck_report(self) -> str:
        """Every request still in flight, one line each with worker,
        tenant and last lifecycle state — a max-steps hang must be
        diagnosable from the exception message alone (ISSUE 9)."""
        from .qos import tenant_of

        def line(where, req, health):
            tr = getattr(req, "trace", None)
            rid = tr.request_id if tr is not None else id(req)
            state = (tr.events[-1][0]
                     if tr is not None and tr.events else "?")
            return (f"  {where}[{health}] req={rid} "
                    f"tenant={tenant_of(req)!r} state={state}")

        lines = []
        for w in self.workers:
            health = "healthy" if w.healthy else (
                w.fail_reason or "unhealthy")
            for req in w.pending:
                lines.append(line(f"{w.wid} routed", req, health))
            sch = w.engine._sched
            if sch is not None:
                for req in sch.requests():
                    lines.append(line(f"{w.wid} scheduled", req,
                                      health))
            for row in w.engine._rows:
                if row is not None:
                    lines.append(line(f"{w.wid} running", row["req"],
                                      health))
        with self._lock:
            parked = list(self._parked)
        for req in parked:
            lines.append(line(
                f"parked(from {getattr(req, '_parked_from', None)})",
                req, "no_healthy_workers"))
        if self._qos_gate is not None:
            for req in self._qos_gate.held():
                lines.append(line("qos gate", req, "throttled"))
        return "\n".join(lines) if lines else "  (none attributable)"

    def run_until_drained(self, max_steps=10_000) -> int:
        """Step until no healthy worker has work. Returns steps taken."""
        steps = 0
        while self.pending_work():
            if steps >= max_steps:
                raise RuntimeError(
                    f"fleet not drained after {max_steps} steps "
                    f"({self.pending_work()} requests in flight); "
                    f"stuck work:\n{self._stuck_report()}")
            self.step()
            steps += 1
        return steps

    # -- watchdogs ----------------------------------------------------------
    def check_watchdogs(self, now=None) -> list:
        """Deterministic stall poll across workers (tests drive
        ``now=`` by hand). Fired stalls flag workers via ``on_stall``;
        the NEXT :meth:`step` runs the failover."""
        fired = []
        for w in self.workers:
            if not w.healthy:
                continue
            info = w.watchdog.check(now=now)
            if info is not None:
                fired.append((w.wid, info))
        return fired

    def start_watchdogs(self):
        """Opt-in background polling (daemon threads; the synchronous
        test path uses :meth:`check_watchdogs` instead)."""
        for w in self.workers:
            w.watchdog.start()
        return self

    # -- observability ------------------------------------------------------
    def aggregator(self):
        """Fresh :class:`MetricsAggregator` over every worker registry
        (dead workers included — their final counters are part of the
        fleet story) plus this fleet's own router registry and, when
        enabled, the shipper's self-observation registry. With QoS,
        per-tenant registries ride along as ``tenant="..."``-labeled
        sample sets (ISSUE 6)."""
        from .fleet_metrics import MetricsAggregator
        agg = MetricsAggregator()
        for w in self.workers:
            agg.add(w.wid, w.registry)
            if w.legacy_snap is not None:
                agg.add_baseline(w.legacy_snap)
        agg.add("router", self.metrics)
        if self.shipper is not None:
            agg.add("shipper", self.shipper.registry)
        if self.qos is not None:
            for tenant, reg in sorted(self.qos.registries().items()):
                agg.add_labels({"tenant": tenant}, reg)
        return agg

    def merged_snapshot(self) -> dict:
        """Union-equivalent merge of every worker registry snapshot
        (the SLO engine's observation unit), plus the counter/histogram
        baselines of pre-restart incarnations — a restart must not
        reset fleet-level totals out from under burn-rate rules."""
        return merge_snapshots(
            [w.registry.snapshot() for w in self.workers]
            + [w.legacy_snap for w in self.workers
               if w.legacy_snap is not None])

    # -- postmortem bundles (ISSUE 13) ---------------------------------------
    def dump_postmortem(self, reason="manual"):
        """Write one postmortem bundle (flight ring, merged registry
        snapshot, scheduler/worker state, last-N request traces,
        per-worker compile logs, fleet config) into ``postmortem_dir``;
        returns the path, or None when disabled or the dump failed.
        Invoked automatically from the watchdog ``on_stall``, the
        restart harvest, and poison quarantine; safe to call by hand.

        MUST NOT take the fleet lock: the restart/poison triggers run
        with it held, the stall trigger without — every read below is
        either lock-free by design (worker registries lock themselves,
        the trace deque only appends) or a point-in-time scalar where a
        torn read costs nothing."""
        if self.postmortem_dir is None:
            return None
        try:
            traces = list(self._traces)[-64:]
        except RuntimeError:            # deque mutated mid-copy: the
            traces = []                 # bundle just loses its traces
        compile_log = []
        state_workers = {}
        for w in self.workers:
            ct = getattr(w.engine, "compiles", None)
            if ct is not None:
                compile_log.extend({**e, "worker": w.wid}
                                   for e in ct.compile_log())
            state_workers[w.wid] = {
                "healthy": w.healthy, "fail_reason": w.fail_reason,
                "restarts": w.restarts, "probation": w.probation,
                "pending": len(w.pending),
                "occupancy": w.occupancy,
                "backlog": w.engine.backlog,
            }
        state = {"degradation": self._degradation,
                 "load_penalty": self.load_penalty,
                 "slo": self.slo.states() if self.slo is not None
                 else None,
                 "workers": state_workers}
        if self._prof is not None:
            state["router_profile"] = self._prof.summary()
        config = {"n_workers": len(self.workers),
                  "policy": self.policy,
                  "tp_degree": self.tp_degree or 1,
                  "seq_degree": self.seq_degree or 1,
                  "max_retries": self.max_retries,
                  "engine_kwargs": dict(self._engine_kw)}
        return dump_postmortem(
            self.postmortem_dir, reason=reason, recorder=self.flight,
            registry=self.merged_snapshot(), traces=traces,
            compile_log=compile_log, config=config, state=state,
            keep=self.postmortem_keep)

    def mark_warm(self) -> int:
        """Declare compile warmup over on every profiled worker: any
        compiled-program signature FIRST seen after this call counts
        as an unexpected post-warmup recompile (the
        ``engine_unexpected_compiles`` gauge — runtime twin of the
        static SC06 bucket checker; attach an SLO ``value`` rule to
        alert on it). Returns the number of trackers armed."""
        n = 0
        for w in self.workers:
            ct = getattr(w.engine, "compiles", None)
            if ct is not None:
                ct.warmup_done()
                n += 1
        return n

    def _sweep_traces(self) -> list[dict]:
        """Move freshly-terminal traces to the unshipped summary list;
        returns the summaries accumulated so far (without clearing)."""
        with self._lock:
            still = []
            for tr in self._open_traces:
                if tr.terminal is not None:
                    self._retired_unshipped.append(tr.summary())
                else:
                    still.append(tr)
            self._open_traces = still
            return list(self._retired_unshipped)

    # -- SLO engine (ISSUE 5) ------------------------------------------------
    def enable_slo(self, rules=None, on_alert=None,
                   load_penalty_boost=4.0, shed=False,
                   shed_target_backlog=None):
        """Attach a :class:`~paddle_tpu.observability.SLOEngine`.

        ``rules`` defaults to a serving triple: TTFT p99 < 0.5 s,
        error rate < 1 %, queue-wait p50 < 1 s (30 s windows). The
        built-in alert hook closes the control loop: while ANY alert
        fires, the affinity router's ``load_penalty`` is multiplied by
        ``load_penalty_boost`` (spread load away from hot workers —
        cached-prefix affinity only wins when it clearly beats the
        imbalance); it is restored when the last alert resolves.
        ``on_alert`` is called after the built-in hook with the same
        transition dict. Drive evaluation with :meth:`check_slo`.

        ISSUE 9 extends the control loop into a DEGRADATION LADDER:
        every :meth:`check_slo` evaluation while any alert fires
        escalates one level (capped at 3) — level 1 is the load
        penalty boost above, level 2 additionally disables
        speculative decode on every worker, level 3 additionally
        halves each worker's per-step token budget (never below one
        decode chunk). The first evaluation with nothing firing
        restores every knob (``fleet_degradation_level`` gauges the
        ladder; each transition is logged and trace-evented).

        ``shed=True`` (ISSUE 6; requires a fleet constructed with
        ``qos=``) arms load shedding: while any alert fires, each
        :meth:`step` sheds pending work above ``shed_target_backlog``
        (default: total fleet slot capacity) — lowest tier first,
        never below a tenant's ``shed_floor``."""
        from ..observability import SLOEngine, SLORule
        if shed and self.qos is None:
            raise ValueError(
                "shed=True requires a fleet constructed with qos= "
                "(the shed planner needs tenant tiers and floors)")
        self._shed = bool(shed)
        self._shed_target = (int(shed_target_backlog)
                             if shed_target_backlog is not None
                             else sum(w.engine.capacity
                                      for w in self.workers))
        if rules is None:
            rules = [
                SLORule("ttft_p99", "engine_ttft_seconds", "p99",
                        threshold=0.5, window_s=30.0, for_s=5.0,
                        clear_for_s=10.0),
                SLORule("error_rate", "engine_failed_total", "ratio",
                        threshold=0.01, window_s=30.0, for_s=5.0,
                        clear_for_s=10.0,
                        total=("engine_retired_total",
                               "engine_failed_total")),
                SLORule("queue_wait_p50", "engine_queue_wait_seconds",
                        "p50", threshold=1.0, window_s=30.0, for_s=5.0,
                        clear_for_s=10.0),
            ]
        boost = float(load_penalty_boost)

        def _hook(info):
            if self.slo is not None and self.slo.firing():
                self.load_penalty = self._base_load_penalty * boost
            else:
                self.load_penalty = self._base_load_penalty
            log_kv(_log, "slo_alert", level=logging.WARNING,
                   rule=info["rule"], state=info["state"],
                   measured=info["measured"],
                   burn_rate=info["burn_rate"],
                   load_penalty=self.load_penalty)
            log_event("fleet_slo_alert", **{
                k: info[k] for k in ("rule", "state", "measured")})
            if on_alert is not None:
                on_alert(info)

        self._deg_boost = boost
        self.slo = SLOEngine(rules, on_alert=_hook,
                             registry=self.metrics)
        return self.slo

    def check_slo(self, now=None) -> list[dict]:
        """Observe the merged worker snapshot, then advance the alert
        state machines. ``now=`` makes replay deterministic (tests
        inject the clock, same discipline as ``check_watchdogs``)."""
        if self.slo is None:
            return []
        self.slo.observe(self.merged_snapshot(), now_=now)
        out = self.slo.check(now_=now)
        # degradation ladder (ISSUE 9): one deterministic escalation
        # per firing evaluation, full restore on the first clean one
        self._set_degradation(
            min(3, self._degradation + 1) if self.slo.firing() else 0)
        return out

    # -- degradation ladder (ISSUE 9) ---------------------------------------
    def _set_degradation(self, level: int) -> None:
        if level == self._degradation:
            return
        old, self._degradation = self._degradation, level
        # lever 1 — router load penalty (the alert hook also maintains
        # this on transitions; both write the same value)
        self.load_penalty = self._base_load_penalty * (
            self._deg_boost if level >= 1 else 1.0)
        for w in self.workers:
            if w.healthy:
                self._apply_degradation_worker(w)
        log_kv(_log, "degradation", level=logging.WARNING,
               old=old, new=level, load_penalty=self.load_penalty)
        log_event("fleet_degradation", old=old, new=level)
        self.flight.record("degradation", old=old, new=level)

    def _apply_degradation_worker(self, w: _Worker) -> None:
        """Apply the CURRENT ladder level to one worker's engine —
        called on every transition and on worker rejoin (a restarted
        engine must join at the fleet's current brownout level). The
        engine's original knobs are saved on first touch and restored
        verbatim at level 0 ("fully restored on resolve")."""
        eng = w.engine
        if self._degradation == 0:
            if w.deg_saved is not None:
                eng.spec_decode = w.deg_saved["spec_decode"]
                eng.step_budget = w.deg_saved["step_budget"]
                w.deg_saved = None
            return
        if w.deg_saved is None:
            w.deg_saved = {"spec_decode": eng.spec_decode,
                           "step_budget": eng.step_budget}
        # lever 2 — speculative decode off (verify steps burn budget
        # on drafts that overload traffic rarely accepts)
        eng.spec_decode = (False if self._degradation >= 2
                           else w.deg_saved["spec_decode"])
        # lever 3 — halve the per-step token budget, never below one
        # decode chunk (brownout: trade throughput for stability)
        eng.step_budget = (
            max(eng.chunk, w.deg_saved["step_budget"] // 2)
            if self._degradation >= 3 else w.deg_saved["step_budget"])

    # -- off-host telemetry (ISSUE 5) ---------------------------------------
    def enable_shipper(self, sinks, interval_s=5.0, **kw):
        """Attach a :class:`~paddle_tpu.observability.TelemetryShipper`
        flushing the merged fleet snapshot + freshly-retired trace
        summaries to ``sinks`` every ``interval_s`` (driven by
        :meth:`step` via ``tick()`` — no extra thread unless you call
        ``shipper.start()`` yourself)."""
        from ..observability import TelemetryShipper
        self.shipper = TelemetryShipper(
            collect=self._collect_telemetry, sinks=sinks,
            interval_s=interval_s, **kw)
        return self.shipper

    def _collect_telemetry(self) -> dict:
        self._sweep_traces()
        with self._lock:
            traces, self._retired_unshipped = \
                self._retired_unshipped, []
        payload = {"kind": "fleet_telemetry",
                   "snapshot": self.merged_snapshot(),
                   "traces": traces}
        if self.slo is not None:
            payload["slo"] = self.slo.states()
        return payload

    # -- cross-worker Chrome timeline (ISSUE 5) ------------------------------
    def worker_pids(self) -> dict:
        """Stable Chrome-lane assignment: pid 0 = router/host, pid i+1
        = worker i."""
        pids = {None: 0, "router": 0}
        for i, w in enumerate(self.workers):
            pids[w.wid] = i + 1
        return pids

    def export_chrome_timeline(self, path, profiler=None) -> str:
        """One ``chrome://tracing`` JSON with a LANE (pid) PER WORKER:
        every retained request trace renders its lifecycle instants +
        worker-residency spans in the owning worker's lane (failover
        hops jump lanes mid-trace), and a recording
        :class:`~paddle_tpu.profiler.Profiler`'s spans merge in —
        engine spans carry ``worker=`` attribution, so prefill/decode
        timing lands in the same lanes (both clocks are
        ``perf_counter``-based, so timestamps align)."""
        pids = self.worker_pids()
        pid_for = lambda w: pids.get(w, 0)          # noqa: E731
        events = [{"name": "process_name", "ph": "M", "pid": 0,
                   "args": {"name": "router"}}]
        for w in self.workers:
            events.append({"name": "process_name", "ph": "M",
                           "pid": pids[w.wid],
                           "args": {"name": f"worker {w.wid}"}})
        with self._lock:
            traces = list(self._traces)
        for tr in traces:
            events.extend(tr.to_events(pid_for=pid_for))
        if profiler is not None:
            for s in profiler._spans:
                base = {"name": s.name, "pid": pid_for(s.worker),
                        "tid": s.tid, "cat": s.kind}
                if s.kind == "op":
                    events.append({**base, "ph": "i", "s": "t",
                                   "ts": s.start_ns / 1e3})
                else:
                    events.append({**base, "ph": "X",
                                   "ts": s.start_ns / 1e3,
                                   "dur": (s.end_ns - s.start_ns) / 1e3})
        # ISSUE 13: step-phase lanes ride the same perf_counter
        # timebase — each profiled worker's admission/launch/publish
        # spans render beside its request traces, the router's
        # schedule/telemetry spans in lane 0
        if self._prof is not None:
            events.extend(self._prof.to_events(pid=0))
        for w in self.workers:
            sp = getattr(w.engine, "profile", None)
            if sp is not None:
                events.extend(sp.to_events(pid=pids[w.wid]))
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def debug_surface(self) -> dict:
        """Named providers for the debug HTTP routes (ISSUE 13): each
        value is a zero-arg callable returning a JSON-able dict,
        evaluated per request on the scrape thread."""
        return {"statusz": self._statusz,
                "requestz": self._requestz,
                "flightz": self.flight.snapshot,
                "compilez": self._compilez}

    def _statusz(self) -> dict:
        out = {"stats": self.stats(),
               "degradation": self._degradation,
               "load_penalty": self.load_penalty,
               "slo": self.slo.states() if self.slo is not None
               else None,
               "flight_seen": len(self.flight)}
        if self._prof is not None:
            out["router_profile"] = self._prof.summary()
            out["worker_profiles"] = {
                w.wid: w.engine.profile.summary()
                for w in self.workers
                if getattr(w.engine, "profile", None) is not None}
        return out

    def _requestz(self) -> dict:
        try:
            traces = list(self._traces)[-64:]
        except RuntimeError:
            traces = []
        return {"count": len(traces),
                "traces": [t.summary() for t in traces]}

    def _compilez(self) -> dict:
        out = {}
        for w in self.workers:
            ct = getattr(w.engine, "compiles", None)
            if ct is not None:
                out[w.wid] = {"stats": ct.stats(),
                              "log": ct.compile_log()}
        return out

    def serve_metrics(self, host="127.0.0.1", port=0):
        """Start the stdlib scrape endpoint (GET /metrics → labeled
        Prometheus text, /metrics.json → merged JSON snapshot, plus
        the ISSUE 13 debug routes /statusz /requestz /flightz
        /compilez). Returns the server; ``.port`` holds the bound port
        when ``port=0``."""
        from .fleet_metrics import MetricsHTTPServer
        if self._http is None:
            self._http = MetricsHTTPServer(
                self.aggregator(), host=host, port=port,
                debug=self.debug_surface()).start()
        return self._http

    def stats(self) -> dict:
        with self._lock:
            n_parked = len(self._parked)
        s = {
            "policy": self.policy,
            "submitted": int(self._c_submitted.value),
            "affinity_hits": int(self._c_affinity_hits.value),
            "failovers": int(self._c_failovers.value),
            "rerouted": int(self._c_rerouted.value),
            "restarts": int(self._c_restarts.value),
            "poisoned": int(self._c_poisoned.value),
            "parked": n_parked,
            "migrations": int(self._c_migrations.value),
            "migrated_pages": int(self._c_migrated_pages.value),
            "stale_hints": int(self._c_stale_hints.value),
            "roles": ({w.wid: w.role for w in self.workers}
                      if self.roles is not None else None),
            "degradation": self._degradation,
            "healthy_workers": sum(1 for w in self.workers if w.healthy),
            "tp_degree": self.tp_degree or 1,
            "seq_degree": self.seq_degree or 1,
            "directory": self.directory.stats(),
            "workers": {w.wid: w.engine.stats() for w in self.workers},
        }
        if self.qos is not None:
            s["shed"] = int(self._c_shed.value)
            s["qos_rejected"] = int(self._c_qos_rejected.value)
            s["qos"] = self.qos.stats()
        return s

    def close(self):
        for w in self.workers:
            w.watchdog.stop()
        if self.shipper is not None:
            # ISSUE 9 satellite: best-effort final drain of queued
            # telemetry through whichever sinks still accept it
            self.shipper.close()
        if self._http is not None:
            self._http.close()
            self._http = None

"""Self-healing fleet under deterministic fault injection (ISSUE 9):
seeded FaultPlan schedules, the FaultInjector's fleet hooks
(worker_crash / worker_hang / alloc_oom / sink_fail), worker restart &
rejoin (manual + auto with capped backoff on an injected clock),
poison-request quarantine with innocent bystanders completing
bit-identical, total-outage parking with unpark-on-rejoin, and the
SLO-driven degradation ladder.

The determinism contract under test: chaos disabled (the default
``fleet.chaos is None``) OR an installed injector with an EMPTY plan
leaves fleet outputs bit-identical to the r13 seed behaviour, and the
whole fault machinery runs on the fleet STEP INDEX plus injected
clocks — no wall time anywhere (see test_no_adhoc_timers)."""

import numpy as np
import pytest

from paddle_tpu.inference.chaos import (FAULT_KINDS, ChaosPoisonError,
                                        FaultEvent, FaultInjector,
                                        FaultPlan)
from paddle_tpu.inference.fleet import (NoHealthyWorkersError,
                                        RequestPoisonedError,
                                        RestartPolicy, ServingFleet)

from harness import ENGINE_KW, shared_model, solo_generate


def _out(req, timeout=60):
    return np.asarray(req.wait(timeout=timeout)).reshape(-1)


class TestFaultPlan:
    def test_seeded_schedule_is_deterministic(self):
        a = FaultPlan.random(7, 200, ["w0", "w1"], rate=0.1)
        b = FaultPlan.random(7, 200, ["w0", "w1"], rate=0.1)
        assert len(a) > 0
        assert a.signature() == b.signature()
        c = FaultPlan.random(8, 200, ["w0", "w1"], rate=0.1)
        assert c.signature() != a.signature()

    def test_event_validation(self):
        with pytest.raises(ValueError, match="kind"):
            FaultEvent(0, "meteor_strike")
        with pytest.raises(ValueError):
            FaultEvent(-1, "worker_crash")
        with pytest.raises(ValueError):
            FaultEvent(0, "worker_hang", duration=0)
        assert set(FAULT_KINDS) == {"worker_crash", "worker_hang",
                                    "slow_step", "alloc_oom",
                                    "sink_fail", "migration_fail"}
        # FaultPlan.random's DEFAULT draw set stays the r14 five: a
        # wider uniform draw would reshuffle every seeded plan and
        # break the chaos preset's pinned replay signatures (r19)
        from paddle_tpu.inference.chaos import RANDOM_KINDS
        assert RANDOM_KINDS == ("worker_crash", "worker_hang",
                                "slow_step", "alloc_oom", "sink_fail")

    def test_events_sorted_and_indexed_by_step(self):
        plan = FaultPlan([FaultEvent(5, "worker_hang", "w0"),
                          FaultEvent(2, "worker_crash", "w1")])
        assert [e.step for e in plan.events] == [2, 5]
        assert [e.kind for e in plan.at(5)] == ["worker_hang"]
        assert plan.at(3) == []


class TestChaosDisabledBitIdentical:
    def test_default_and_empty_plan_leave_outputs_bit_identical(self):
        """The r13 regression: a fleet without chaos (the default) and
        one with an installed injector whose plan is EMPTY must produce
        byte-for-byte the same tokens — and both must match the
        single-engine oracle."""
        m = shared_model()
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (8, 11)]

        def run(install_empty):
            fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                                 engine_kwargs=ENGINE_KW)
            if install_empty:
                inj = FaultInjector(FaultPlan([])).install(fleet)
                assert fleet.chaos is inj
            else:
                assert fleet.chaos is None
            reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
            fleet.run_until_drained()
            outs = [_out(r) for r in reqs]
            fired = fleet.chaos.fired if fleet.chaos is not None else []
            fleet.close()
            return outs, fired

        base, _ = run(False)
        empty, fired = run(True)
        assert fired == []
        for a, b, p in zip(base, empty, prompts):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(
                a, solo_generate(m, p, 6).reshape(-1))


class TestInjectedFaults:
    def test_worker_crash_fails_over_and_auto_restarts(self):
        """ISSUE 9 acceptance: capacity provably returns to N within
        the backoff bound, the prefix directory re-registers the
        rejoined worker, and every request still completes
        bit-identical to the solo oracle."""
        m = shared_model()
        rng = np.random.RandomState(4)
        vt = [0.0]
        fleet = ServingFleet(
            m, n_workers=2, policy="round_robin", engine_kwargs=ENGINE_KW,
            restart=RestartPolicy(auto=True, backoff_base_s=1.0,
                                  clock=lambda: vt[0]))
        inj = FaultInjector(
            FaultPlan([FaultEvent(1, "worker_crash", "w1")])).install(fleet)
        reqs, expect = [], []
        for _ in range(4):
            p = rng.randint(1, 128, (10,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=12))
            expect.append(solo_generate(m, p, 12))
        fleet.step()                    # step 0: both workers admit
        vt[0] += 0.25
        fleet.step()                    # step 1: w1 crashes mid-step
        assert not fleet.workers[1].healthy
        assert fleet.stats()["failovers"] == 1
        # backoff bound: first restart is backoff_s(0) = 1.0s after the
        # drain is observed — at 0.25s/step that is <= 6 steps away
        steps = 0
        while not fleet.workers[1].healthy:
            vt[0] += 0.25
            fleet.step()
            steps += 1
            assert steps <= 6, "restart missed the backoff bound"
        st = fleet.stats()
        assert st["healthy_workers"] == 2
        assert st["restarts"] == 1
        assert fleet.workers[1].restarts == 1
        # rejoin re-registered the directory listener under the same wid
        assert "w1" in fleet.directory.stats()
        fleet.run_until_drained()
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(_out(r), e.reshape(-1))
        assert inj.fired == [(1, "worker_crash", "w1")]
        # probation burns down one healthy step at a time (the drain may
        # finish first — idle steps burn it too)
        fleet.step()
        fleet.step()
        assert fleet.workers[1].probation == 0
        fleet.close()

    def test_worker_hang_freezes_heartbeat_until_watchdog_fires(self):
        """A hang is NOT a crash: the worker raises nothing, its
        device-steps heartbeat just stops. The stall watchdog is the
        component that must notice — same detection path as a real
        wedged device loop."""
        m = shared_model()
        rng = np.random.RandomState(5)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             stall_s=5.0, engine_kwargs=ENGINE_KW)
        inj = FaultInjector(FaultPlan(
            [FaultEvent(1, "worker_hang", "w0", duration=1000)]))
        inj.install(fleet)
        reqs, expect = [], []
        for _ in range(2):
            p = rng.randint(1, 128, (8,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=10))
            expect.append(solo_generate(m, p, 10))
        fleet.step()                            # step 0: both decode
        assert fleet.check_watchdogs(now=50.0) == []    # baseline
        fleet.step()                            # step 1: w0 hung
        assert inj.suppress_step(fleet.workers[0])
        fired = fleet.check_watchdogs(now=56.0)         # > stall_s
        assert [wid for wid, _ in fired] == ["w0"]
        assert not fleet.workers[0].healthy
        assert fleet.workers[0].fail_reason == "stall"
        fleet.run_until_drained()               # survivor drains all
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(_out(r), e.reshape(-1))
        assert fleet.stats()["failovers"] == 1
        # a stall says nothing about WHICH request is poison: no blame
        assert all(getattr(r, "retry_count", 0) == 0 for r in reqs)
        fleet.close()

    def test_alloc_oom_surfaces_as_step_fault(self):
        """An injected allocator OOM raises out of ``admit`` inside the
        worker step — the fleet must treat it exactly like any other
        raising step (fail the WORKER, re-route, finish elsewhere)."""
        m = shared_model()
        rng = np.random.RandomState(6)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        FaultInjector(FaultPlan(
            [FaultEvent(0, "alloc_oom", "w0")])).install(fleet)
        p = rng.randint(1, 128, (10,)).astype(np.int32)
        req = fleet.submit(p, max_new_tokens=8)     # round-robin -> w0
        expect = solo_generate(m, p, 8)
        fleet.run_until_drained()
        np.testing.assert_array_equal(_out(req), expect.reshape(-1))
        assert not fleet.workers[0].healthy
        assert fleet.workers[0].fail_reason == "drained"
        assert fleet.stats()["failovers"] == 1
        fleet.close()

    def test_sink_fail_window_then_delivery_resumes(self):
        """During the window every sink emit raises (counted, payloads
        retained under backoff); after the window expires the original
        sink is restored and the queue drains."""

        class _ListSink:
            def __init__(self):
                self.payloads = []

            def emit(self, payload):
                self.payloads.append(payload)

        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        rec = _ListSink()
        fleet.enable_shipper([rec], interval_s=1e9)
        FaultInjector(FaultPlan(
            [FaultEvent(1, "sink_fail", duration=2)])).install(fleet)
        fleet.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        fleet.step()                    # step 0: first tick flushes
        n0 = len(rec.payloads)
        assert n0 >= 1
        fleet.step()                    # step 1: sinks wrapped
        fleet.shipper.enqueue({"probe": 1})
        assert fleet.shipper.flush(now_=1000.0) == 0
        assert fleet.shipper.stats()["sink_errors"] >= 1
        assert len(rec.payloads) == n0          # nothing leaked through
        fleet.step()                    # step 2: window still open
        fleet.step()                    # step 3: sink restored
        assert fleet.shipper.flush(now_=2000.0) >= 1    # past backoff
        assert any("probe" in p for p in rec.payloads)
        fleet.run_until_drained()
        fleet.close()


class TestRestartAndRejoin:
    def test_restart_worker_rebuilds_and_directory_repopulates(self):
        m = shared_model()
        rng = np.random.RandomState(7)
        fleet = ServingFleet(m, n_workers=2, policy="affinity",
                             engine_kwargs=ENGINE_KW)
        p = rng.randint(1, 128, (16,)).astype(np.int32)
        req = fleet.submit(p, max_new_tokens=4)
        fleet.run_until_drained()
        req.wait(timeout=60)
        stats = fleet.directory.stats()
        owner = max(stats, key=lambda w: stats[w])
        assert stats[owner] > 0         # retire published the prefix
        old_engine = next(w.engine for w in fleet.workers
                          if w.wid == owner)
        fleet.kill_worker(owner)
        assert owner not in fleet.directory.stats()     # index wiped
        n = fleet.restart_worker(owner)
        assert n == 1
        w = next(x for x in fleet.workers if x.wid == owner)
        assert w.healthy and w.engine is not old_engine
        assert fleet.stats()["healthy_workers"] == 2
        assert fleet.directory.stats()[owner] == 0      # re-registered
        assert w.probation == 2
        # the same prefix republished through the NEW cache shows up in
        # the directory again — the listener really was re-wired
        tail = rng.randint(1, 128, (4,)).astype(np.int32)
        req2 = fleet.submit(np.concatenate([p, tail]), max_new_tokens=4)
        fleet.run_until_drained()
        req2.wait(timeout=60)
        assert sum(fleet.directory.stats().values()) > 0
        fleet.close()

    def test_restart_rejects_healthy_and_unknown_workers(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        with pytest.raises(RuntimeError, match="healthy"):
            fleet.restart_worker("w0")
        with pytest.raises(ValueError, match="unknown worker"):
            fleet.restart_worker("w99")
        fleet.close()

    def test_probation_excludes_rejoined_worker_from_routing(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        fleet.kill_worker("w1")
        fleet.restart_worker("w1")
        w1 = fleet.workers[1]
        assert w1.probation == 2
        for _ in range(3):
            fleet.submit(np.arange(1, 9, dtype=np.int32),
                         max_new_tokens=2)
        # warm-up window: the router skips the rejoined worker
        assert len(fleet.workers[0].pending) == 3
        assert len(w1.pending) == 0
        fleet.run_until_drained()
        assert w1.probation == 0        # burned down by healthy steps
        fleet.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
        fleet.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
        assert [len(w.pending) for w in fleet.workers] == [1, 1]
        fleet.run_until_drained()
        fleet.close()

    def test_counters_survive_restart(self):
        """Fleet-level totals must NOT reset when a worker's registry
        is replaced on restart (the chaos bench caught exactly this:
        every worker restarted during the run and the final snapshot
        claimed zero retires). The dead incarnation's counters fold
        into the merge; its gauges die with it."""
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        req = fleet.submit(np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=4)
        fleet.run_until_drained()
        req.wait(timeout=60)
        before = fleet.merged_snapshot()["counters"]["engine_retired_total"]
        assert before >= 1
        for wid in ("w0", "w1"):
            fleet.kill_worker(wid)
            fleet.restart_worker(wid)
        snap = fleet.merged_snapshot()
        assert snap["counters"]["engine_retired_total"] == before
        agg = fleet.aggregator().snapshot()
        assert agg["fleet"]["counters"]["engine_retired_total"] == before
        # gauges come only from the LIVE incarnations — no double count
        live = sum(w.registry.snapshot()["gauges"].get(
            "engine_backlog", 0.0) for w in fleet.workers)
        assert snap["gauges"]["engine_backlog"] == live
        fleet.close()

    def test_max_restarts_caps_flapping(self):
        m = shared_model()
        vt = [0.0]
        fleet = ServingFleet(
            m, n_workers=2, engine_kwargs=ENGINE_KW,
            restart=RestartPolicy(auto=True, backoff_base_s=0.0,
                                  max_restarts=1, clock=lambda: vt[0]))
        fleet.kill_worker("w0")
        fleet.step()                    # schedules restart_at
        vt[0] += 1.0
        fleet.step()                    # restart #1
        assert fleet.workers[0].healthy
        fleet.kill_worker("w0")
        for _ in range(3):
            vt[0] += 1.0
            fleet.step()
        assert not fleet.workers[0].healthy     # cap: stays dead
        assert fleet.workers[0].restarts == 1
        assert fleet.stats()["restarts"] == 1
        fleet.close()

    def test_backoff_is_capped_exponential(self):
        pol = RestartPolicy(backoff_base_s=0.5, backoff_max_s=4.0)
        assert [pol.backoff_s(n) for n in range(5)] == \
            [0.5, 1.0, 2.0, 4.0, 4.0]


class TestPoisonQuarantine:
    def test_poison_cascade_is_quarantined_and_innocents_bitmatch(self):
        """ISSUE 9 acceptance: one request that crashes every worker it
        is admitted on must end with RequestPoisonedError after
        max_retries re-routes — with ALL workers healthy again (auto
        restart) and every innocent request's output bit-identical to
        the fault-free oracle."""
        m = shared_model()
        rng = np.random.RandomState(9)
        fleet = ServingFleet(
            m, n_workers=3, policy="round_robin", engine_kwargs=ENGINE_KW,
            restart=RestartPolicy(auto=True, backoff_base_s=0.0))
        # empty plan + poison token: the only faults are the ones the
        # poison request itself causes
        FaultInjector(FaultPlan([]), poison_token=120).install(fleet)
        innocents, expect = [], []
        for _ in range(4):
            p = rng.randint(1, 100, (10,)).astype(np.int32)    # no 120
            innocents.append(fleet.submit(p, max_new_tokens=10))
            expect.append(solo_generate(m, p, 10))
        # long enough that the poison can never RETIRE within one step
        # of a re-admission (the crash fires at the NEXT step's chaos
        # check, so a request finishing in its admission step would
        # escape the third attribution)
        poison = fleet.submit(np.array([5, 120, 7, 8], dtype=np.int32),
                              max_new_tokens=40)
        fleet.run_until_drained(max_steps=500)
        with pytest.raises(RequestPoisonedError, match="quarantined"):
            poison.wait(timeout=60)
        # the trace tells the whole story
        tr = poison.trace
        assert tr.attrs["poison_reason"]
        assert tr.count("quarantined") == 1
        assert tr.count("retry") == poison.retry_count == 3
        assert tr.summary()["poison_reason"] is not None
        assert tr.summary()["retries"] == 3
        for r, e in zip(innocents, expect):
            assert getattr(r, "retry_count", 0) <= fleet.max_retries
            np.testing.assert_array_equal(_out(r), e.reshape(-1))
        # the drain ends once the work does — a victim crashed on the
        # final step still has its (zero-backoff) restart pending; a
        # few idle steps let the fleet finish healing
        steps = 0
        while fleet.stats()["healthy_workers"] < 3:
            fleet.step()
            steps += 1
            assert steps < 10
        st = fleet.stats()
        assert st["poisoned"] == 1
        assert st["healthy_workers"] == 3       # every victim restarted
        assert st["restarts"] >= 1
        fleet.close()

    def test_total_outage_parks_then_unparks_on_rejoin(self):
        """Zero healthy workers mid-failover: requests PARK (step never
        raises), submit raises the typed error, and the auto-restarted
        worker unparks everything with a ``restarted`` hop."""
        m = shared_model()
        rng = np.random.RandomState(10)
        vt = [0.0]
        fleet = ServingFleet(
            m, n_workers=1, engine_kwargs=ENGINE_KW,
            restart=RestartPolicy(auto=True, backoff_base_s=1.0,
                                  clock=lambda: vt[0]))
        FaultInjector(FaultPlan(
            [FaultEvent(1, "worker_crash", "w0")])).install(fleet)
        reqs, expect = [], []
        for _ in range(2):
            p = rng.randint(1, 128, (8,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=8))
            expect.append(solo_generate(m, p, 8))
        fleet.step()                    # step 0: admit
        fleet.step()                    # step 1: crash -> nowhere to go
        assert fleet.stats()["healthy_workers"] == 0
        assert fleet.stats()["parked"] == 2
        with pytest.raises(NoHealthyWorkersError):
            fleet.submit(np.arange(1, 5, dtype=np.int32))
        assert fleet.pending_work() >= 2        # parked is still work
        steps = 0
        while fleet.pending_work():
            vt[0] += 0.5
            fleet.step()
            steps += 1
            assert steps < 60
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(_out(r), e.reshape(-1))
        assert any(h["reason"] == "restarted"
                   for r in reqs for h in r.trace.hops)
        st = fleet.stats()
        assert st["parked"] == 0
        assert st["restarts"] == 1
        fleet.close()


class TestDegradationLadder:
    def test_knob_transitions_and_full_restore(self):
        m = shared_model()
        kw = dict(ENGINE_KW, spec_decode=True, step_budget=16)
        fleet = ServingFleet(m, n_workers=2, engine_kwargs=kw)
        fleet.enable_slo()              # default boost 4.0
        base_lp = fleet.load_penalty
        e0 = fleet.workers[0].engine
        gauge = fleet.metrics.get("fleet_degradation_level")
        assert gauge.value == 0
        fleet._set_degradation(1)
        assert gauge.value == 1
        assert fleet.load_penalty == base_lp * 4.0
        assert e0.spec_decode is True and e0.step_budget == 16
        fleet._set_degradation(2)
        assert e0.spec_decode is False and e0.step_budget == 16
        fleet._set_degradation(3)
        assert e0.spec_decode is False
        assert e0.step_budget == 8      # halved, still >= chunk
        fleet._set_degradation(0)       # fully restored on resolve
        assert gauge.value == 0
        assert fleet.load_penalty == base_lp
        assert e0.spec_decode is True and e0.step_budget == 16
        assert fleet.workers[0].deg_saved is None
        fleet.close()

    def test_budget_never_halves_below_chunk(self):
        m = shared_model()
        kw = dict(ENGINE_KW, spec_decode=True, step_budget=6)
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=kw)
        fleet.enable_slo()
        fleet._set_degradation(3)
        assert fleet.workers[0].engine.step_budget == 4     # == chunk
        fleet._set_degradation(0)
        assert fleet.workers[0].engine.step_budget == 6
        fleet.close()

    def test_restarted_worker_joins_at_current_brownout_level(self):
        m = shared_model()
        kw = dict(ENGINE_KW, spec_decode=True, step_budget=16)
        fleet = ServingFleet(m, n_workers=2, engine_kwargs=kw)
        fleet.enable_slo()
        fleet._set_degradation(2)
        fleet.kill_worker("w1")
        fleet.restart_worker("w1")
        e1 = fleet.workers[1].engine
        assert e1.spec_decode is False  # rejoined INTO the brownout
        fleet._set_degradation(0)
        assert e1.spec_decode is True
        fleet.close()

    def test_check_slo_escalates_then_restores(self):
        """The closed loop: a firing backlog alert climbs the ladder one
        level per evaluation; the first clean evaluation restores every
        knob."""
        from paddle_tpu.observability import SLORule
        m = shared_model()
        kw = dict(ENGINE_KW, spec_decode=True, step_budget=16)
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=kw)
        fleet.enable_slo(rules=[SLORule(
            "backlog", "engine_backlog", "value", threshold=0.5,
            op="<", window_s=60.0, for_s=0.5, clear_for_s=1.0)])
        for _ in range(6):              # capacity 2: deep backlog
            fleet.submit(np.arange(1, 9, dtype=np.int32),
                         max_new_tokens=4)
        fleet.step()
        assert fleet.merged_snapshot()["gauges"]["engine_backlog"] > 0.5
        fleet.check_slo(now=0.0)        # breach -> pending
        assert fleet._degradation == 0
        fleet.check_slo(now=1.0)        # for_s held -> firing
        assert fleet._degradation == 1
        fleet.check_slo(now=2.0)
        assert fleet._degradation == 2
        assert fleet.workers[0].engine.spec_decode is False
        fleet.check_slo(now=3.0)
        assert fleet._degradation == 3
        assert fleet.workers[0].engine.step_budget == 8
        fleet.check_slo(now=4.0)
        assert fleet._degradation == 3  # capped
        fleet.run_until_drained()       # backlog clears
        fleet.check_slo(now=10.0)       # clear hysteresis starts
        fleet.check_slo(now=20.0)       # resolved -> restore
        assert fleet._degradation == 0
        assert fleet.workers[0].engine.spec_decode is True
        assert fleet.workers[0].engine.step_budget == 16
        fleet.close()


class TestSatellites:
    def test_no_healthy_workers_error_is_typed(self):
        assert issubclass(NoHealthyWorkersError, RuntimeError)
        assert issubclass(RequestPoisonedError, RuntimeError)
        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        fleet.workers[0].healthy = False
        with pytest.raises(NoHealthyWorkersError, match="no healthy"):
            fleet.submit(np.arange(1, 5, dtype=np.int32))
        fleet.close()

    def test_shipper_close_flushes_and_counts_drops(self):
        from paddle_tpu.observability import TelemetryShipper

        class _ListSink:
            def __init__(self):
                self.payloads = []

            def emit(self, payload):
                self.payloads.append(payload)

        class _BoomSink:
            def __init__(self):
                self.calls = 0

            def emit(self, payload):
                self.calls += 1
                raise OSError("dead sink")

        good, bad = _ListSink(), _BoomSink()
        sh = TelemetryShipper(sinks=[good, bad], interval_s=1e9)
        for i in range(3):
            sh.enqueue({"i": i})
        assert good.payloads == []      # nothing flushed yet
        counts = sh.close()
        assert [p["i"] for p in good.payloads] == [0, 1, 2]
        assert counts["flushed"] == 3
        assert counts["dropped"] == 3   # the dead sink's whole queue
        assert bad.calls == 1           # abandoned at first failure
        assert sh.stats()["shipped"] == 3
        assert sh.stats()["dropped"] == 3

    def test_fleet_close_runs_final_flush(self):
        class _ListSink:
            def __init__(self):
                self.payloads = []

            def emit(self, payload):
                self.payloads.append(payload)

        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        rec = _ListSink()
        fleet.enable_shipper([rec], interval_s=1e9)
        req = fleet.submit(np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=2)
        fleet.run_until_drained()
        req.wait(timeout=60)
        fleet.shipper.enqueue({"final": True})
        fleet.close()
        assert any(p.get("final") for p in rec.payloads)

    def test_run_until_drained_reports_stuck_work(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        fleet.submit(np.arange(1, 9, dtype=np.int32),
                     max_new_tokens=4, tenant="acme")
        fleet.kill_worker("w0")         # parks it; no restart policy
        with pytest.raises(RuntimeError) as ei:
            fleet.run_until_drained(max_steps=3)
        msg = str(ei.value)
        assert "stuck work" in msg
        assert "tenant='acme'" in msg
        assert "parked" in msg
        assert "state=" in msg
        fleet.close()

    def test_lifecycle_states_extended_in_order(self):
        from paddle_tpu.observability.tracing import LIFECYCLE_STATES
        i = LIFECYCLE_STATES.index
        assert i("preempted") < i("retry") < i("quarantined") \
            < i("retired") < i("failed")

    def test_summary_appends_new_keys_after_r11(self):
        """Shape-compat: consumers indexing the r11 summary keys
        positionally must be unaffected — the ISSUE 9 keys come LAST."""
        from paddle_tpu.observability import RequestTrace
        tr = RequestTrace(t=0.0)
        keys = list(tr.summary().keys())
        r11 = ["request_id", "state", "ttft_s", "queue_wait_s",
               "preemptions", "decode_chunks", "served_tokens",
               "events", "trace_id", "worker_id", "hops", "attrs",
               "tenant"]
        assert keys[:len(r11)] == r11
        assert keys[len(r11):] == ["retries", "poison_reason"]
        tr.mark("retry")
        tr.mark("retry")
        assert tr.summary()["retries"] == 2
        assert tr.summary()["poison_reason"] is None

    def test_new_counters_and_gauge_registered(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        for name in ("fleet_restarts_total", "fleet_poisoned_total",
                     "fleet_degradation_level"):
            assert fleet.metrics.get(name) is not None
        text = fleet.aggregator().prometheus_text()
        assert "fleet_restarts_total" in text
        assert "fleet_poisoned_total" in text
        assert "fleet_degradation_level" in text
        st = fleet.stats()
        for key in ("restarts", "poisoned", "parked", "degradation"):
            assert key in st
        fleet.close()


class TestPostmortemBundles:
    """ISSUE 13: every injected crash leaves a postmortem bundle whose
    flight ring shows the fault next to the failover it provoked, and
    the whole observability stack (profiler + recorders + bundles)
    never perturbs the token stream."""

    def test_bundle_per_crash_with_bit_identical_outputs(self, tmp_path):
        m = shared_model()
        rng = np.random.RandomState(23)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (8, 11, 6, 9)]
        plan = FaultPlan([FaultEvent(1, "worker_crash", worker="w1"),
                          FaultEvent(2, "worker_crash", worker="w2")])

        def run(with_chaos, pdir=None):
            fleet = ServingFleet(
                m, n_workers=3, policy="round_robin",
                engine_kwargs=ENGINE_KW, profile=with_chaos,
                postmortem_dir=pdir)
            inj = None
            if with_chaos:
                inj = FaultInjector(plan).install(fleet)
            reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
            fleet.run_until_drained()
            outs = [_out(r) for r in reqs]
            faults = fleet.flight.events(kind="fault")
            fleet.close()
            return outs, inj, faults

        base, _, _ = run(False)
        pdir = tmp_path / "bundles"
        outs, inj, faults = run(True, pdir=str(pdir))
        # bit-parity: failover is recompute-resume, the profiler and
        # bundle dumping are pure observers
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        # the flight ring's fault events ARE the plan signature
        assert [(e["step"], e["fault"], e["worker"], e["duration"],
                 e["magnitude"]) for e in faults] == plan.signature()
        assert [(s, k, w) for s, k, w in inj.fired] == \
            [(e.step, e.kind, e.worker) for e in plan.events]
        bundles = sorted(p.name for p in pdir.iterdir()
                         if p.name.startswith("postmortem_"))
        crash_bundles = [b for b in bundles if "failover" in b]
        assert len(crash_bundles) == len(plan)
        import json
        doc = json.loads((pdir / crash_bundles[0]).read_text())
        assert doc["bundle_version"] == 1
        assert doc["reason"].startswith("failover:w1")
        kinds = [e["kind"] for e in doc["flight"]["events"]]
        assert "fault" in kinds and "failover" in kinds
        assert kinds.index("fault") < kinds.index("failover")
        # the bundle carries the observatory: compile log + state
        assert any(e["program"] for e in doc["compile_log"])
        assert set(doc["state"]["workers"]) == {"w0", "w1", "w2"}

    def test_stall_dumps_bundle(self, tmp_path):
        """A tripped stall watchdog triggers a bundle BEFORE the fleet
        harvests the worker (reason ``stall:<wid>``)."""
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             stall_s=1.0, engine_kwargs=ENGINE_KW,
                             postmortem_dir=str(tmp_path))
        plan = FaultPlan([FaultEvent(1, "worker_hang", worker="w0",
                                     duration=50)])
        FaultInjector(plan).install(fleet)
        rng = np.random.RandomState(5)
        reqs = [fleet.submit(rng.randint(1, 128, (7,)).astype(np.int32),
                             max_new_tokens=4) for _ in range(3)]
        t = 0.0
        for _ in range(6):
            fleet.step()
            t += 0.5
            fleet.check_watchdogs(now=t)
        fleet.run_until_drained()
        for r in reqs:
            _out(r)
        fleet.close()
        names = sorted(p.name for p in tmp_path.iterdir())
        assert any("stall-w0" in n for n in names)
        stalls = fleet.flight.events(kind="stall")
        assert stalls and stalls[0]["src"] == "w0"


class TestBundleDeterminism:
    """Two recorders driven by the same scripted clock and events must
    dump byte-identical bundles — the postmortem format carries no
    hidden wall-clock state (sorted keys, injected clocks only)."""

    @staticmethod
    def _scripted(tmpdir):
        from paddle_tpu.observability import (FlightRecorder,
                                              dump_postmortem)
        t = [0.0]

        def clock():
            t[0] += 0.125
            return t[0]

        rec = FlightRecorder(capacity=16, clock=clock, name="w0")
        rec.record("fault", step=3, fault="worker_crash", worker="w0")
        rec.record("failover", worker="w0", rerouted=2, parked=0)
        path = dump_postmortem(
            str(tmpdir), reason="failover:w0", recorder=rec,
            registry={"counters": {"fleet_failovers_total": 1.0},
                      "gauges": {}, "histograms": {}},
            traces=[{"request_id": "r1", "terminal": "retired"}],
            compile_log=[{"program": "decode_chunk", "bucket_key": 4,
                          "wall_s": 0.5, "post_warmup": False}],
            config={"n_workers": 2}, state={"degradation": 0})
        assert path is not None
        return path

    def test_same_script_same_bytes(self, tmp_path):
        a = self._scripted(tmp_path / "a")
        b = self._scripted(tmp_path / "b")
        import pathlib
        pa, pb = pathlib.Path(a), pathlib.Path(b)
        assert pa.name == pb.name
        assert pa.read_bytes() == pb.read_bytes()

    def test_keep_prunes_oldest(self, tmp_path):
        from paddle_tpu.observability import (FlightRecorder,
                                              dump_postmortem)
        rec = FlightRecorder(capacity=4, clock=lambda: 1.0)
        for i in range(5):
            dump_postmortem(str(tmp_path), reason=f"r{i}",
                            recorder=rec, keep=3)
        names = sorted(p.name for p in tmp_path.iterdir())
        assert len(names) == 3
        assert names[-1].endswith("_r4.json")


class TestProfiledFleetBitIdentical:
    """ISSUE 13 acceptance: ``profile=True`` (step profiler + compile
    tracker + always-on flight ring) must leave fleet outputs
    byte-identical to the unprofiled default."""

    def test_profile_on_off_same_tokens(self):
        m = shared_model()
        rng = np.random.RandomState(17)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (5, 12, 9)]

        def run(profile):
            fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                                 engine_kwargs=ENGINE_KW,
                                 profile=profile)
            reqs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
            fleet.run_until_drained()
            outs = [_out(r) for r in reqs]
            fleet.close()
            return outs, fleet

        base, fleet_off = run(False)
        prof, fleet_on = run(True)
        for a, b in zip(base, prof):
            np.testing.assert_array_equal(a, b)
        # off: engines carry no instruments at all
        assert all(w.engine.profile is None and w.engine.compiles is None
                   for w in fleet_off.workers)
        # on: every worker profiled, phases populated, compiles seen
        s = fleet_on.workers[0].engine.profile.summary()
        assert s["steps"] > 0 and "launch" in s["phases"]
        assert fleet_on.workers[0].engine.compiles.stats()["compiles"] > 0
        assert fleet_on.mark_warm() == 2


class TestMigrationFault:
    """ISSUE 14: ``migration_fail`` kills transplants touching the
    faulted worker for the window. A dead transplant must fail BEFORE
    any pages move, and the fleet must fall back to a cold prefill on
    the routed worker — one slower request, never a wrong one."""

    def test_dead_transplant_cold_prefills(self):
        m = shared_model()
        rng = np.random.RandomState(21)
        A = rng.randint(1, 128, (24,)).astype(np.int32)
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             migration_budget_pages=8,
                             load_penalty=100.0)
        plan = FaultPlan([FaultEvent(0, "migration_fail", "w0",
                                     duration=10**6)])
        FaultInjector(plan).install(fleet)
        r1 = fleet.submit(A, max_new_tokens=8)
        fleet.run_until_drained()
        out1 = _out(r1)
        # pile load on the cached worker so the route would migrate
        for n in (16, 16, 16):
            fleet.submit(rng.randint(1, 128, (n,)).astype(np.int32),
                         max_new_tokens=4)
        r2 = fleet.submit(A, max_new_tokens=8)
        st = fleet.stats()
        assert st["migrations"] == 0       # transplant died, no pages
        fails = [e for e in fleet.flight.snapshot()["events"]
                 if e.get("kind") == "kv_migration_failed"]
        assert fails and fails[0]["error"] == "ChaosMigrationError"
        fleet.run_until_drained()
        np.testing.assert_array_equal(out1, _out(r2))  # cold, correct
        np.testing.assert_array_equal(out1, solo_generate(m, A, 8).reshape(-1))
        for w in fleet.workers:
            assert w.engine._alloc.conservation_ok
        fleet.close()

    def test_dead_handoff_keeps_row_on_prefill_worker(self):
        """Role-split under a permanent migration_fail window: every
        handoff dies, rows decode to completion on the prefill worker,
        outputs still match the oracle."""
        m = shared_model()
        rng = np.random.RandomState(22)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (24, 14)]
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             roles=("prefill", "decode"))
        plan = FaultPlan([FaultEvent(0, "migration_fail", "w1",
                                     duration=10**6)])
        FaultInjector(plan).install(fleet)
        reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        fleet.run_until_drained()
        assert fleet.stats()["migrations"] == 0
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                _out(r), solo_generate(m, p, 8).reshape(-1))
        for w in fleet.workers:
            assert w.engine._alloc.conservation_ok
        fleet.close()

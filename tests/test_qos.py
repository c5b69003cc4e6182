"""Multi-tenant QoS (ISSUE 6): token-bucket admission edge cases,
weighted fair-share scheduling (incl. the no-starvation property sim),
SLO-driven shedding with per-tenant floors, submit-path validation,
tenant-labeled telemetry, and the seeded traffic generator.

Everything policy-level runs on injected virtual clocks — no test here
sleeps or reads wall time to make a decision."""

import numpy as np
import pytest

from paddle_tpu.inference.qos import (AdmissionGate, FairShareScheduler,
                                      QoSPolicy, RequestShedError,
                                      TenantPolicy, TokenBucket,
                                      request_cost, tenant_of)
from paddle_tpu.inference.scheduler import RequestScheduler
from paddle_tpu.inference.traffic import (TenantProfile,
                                          TrafficGenerator, jain_index)
from paddle_tpu.observability import RequestTrace

from harness import drive, shared_model, solo_generate


class _FakeReq:
    """Minimal request stand-in for policy-level tests (the real
    ``_Request`` validates prompts and needs numpy ids)."""

    def __init__(self, tenant=None, cost=10, max_new=4, priority=0,
                 seq=None):
        self.ids = np.ones(max(cost - max_new, 1), np.int32)
        self.max_new = max_new
        self.tenant = tenant
        self.priority = priority
        self._sched_seq = seq
        self.trace = RequestTrace(tenant=tenant)


class _VClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# token bucket
# ---------------------------------------------------------------------------
class TestTokenBucket:
    def test_starts_full_and_burst_exhausts(self):
        clk = _VClock()
        b = TokenBucket(rate=5.0, burst=20.0, clock=clk)
        assert b.available() == 20.0
        assert b.try_take(12)
        assert b.try_take(8)
        assert not b.try_take(1)           # burst gone, clock frozen

    def test_refill_integrates_injected_clock_and_caps(self):
        clk = _VClock()
        b = TokenBucket(rate=4.0, burst=10.0, clock=clk)
        assert b.try_take(10)
        clk.t = 1.5
        assert b.available() == pytest.approx(6.0)   # 1.5 s * 4/s
        clk.t = 100.0
        assert b.available() == 10.0        # capped at burst
        assert b.try_take(10) and not b.try_take(0.1)

    def test_explicit_t_overrides_clock(self):
        b = TokenBucket(rate=1.0, burst=4.0, clock=_VClock(), t=0.0)
        assert b.try_take(4, t=0.0)
        assert not b.try_take(2, t=1.0)
        assert b.try_take(2, t=2.0)

    def test_time_never_runs_backwards(self):
        b = TokenBucket(rate=10.0, burst=10.0, clock=_VClock(), t=5.0)
        b.try_take(10, t=5.0)
        assert b.available(t=1.0) == 0.0    # stale t: no negative refill


# ---------------------------------------------------------------------------
# policy validation
# ---------------------------------------------------------------------------
class TestTenantPolicy:
    @pytest.mark.parametrize("kw", [
        dict(on_limit="drop"), dict(rate=0.0), dict(rate=-1.0),
        dict(burst=0.0), dict(weight=-0.5), dict(shed_floor=-1),
    ])
    def test_invalid_fields_raise(self, kw):
        with pytest.raises(ValueError):
            TenantPolicy("t", **kw)

    def test_duplicate_tenant_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            QoSPolicy([TenantPolicy("a"), TenantPolicy("a")])

    def test_non_policy_rejected(self):
        with pytest.raises(TypeError):
            QoSPolicy([{"tenant": "a"}])

    def test_unknown_tenant_falls_back_to_default(self):
        qos = QoSPolicy([TenantPolicy("a", weight=3.0)],
                        default=TenantPolicy(weight=7.0, tier=2))
        assert qos.weight("a") == 3.0
        assert qos.weight("zzz") == 7.0 and qos.tier("zzz") == 2

    def test_tenant_of_and_cost(self):
        r = _FakeReq(cost=12, max_new=4)
        assert tenant_of(r) == "default"
        assert request_cost(r) == 12
        assert tenant_of(_FakeReq(tenant="t9")) == "t9"


# ---------------------------------------------------------------------------
# admission gate
# ---------------------------------------------------------------------------
class TestAdmissionGate:
    def _gate(self, clk, **kw):
        pol = TenantPolicy("a", **kw)
        qos = QoSPolicy([pol], clock=clk)
        return qos, qos.gate()

    def test_zero_weight_rejects_and_counts(self):
        qos = QoSPolicy([TenantPolicy("a", weight=0.0)],
                        clock=_VClock())
        g = qos.gate()
        assert g.decide(_FakeReq(tenant="a")) == ("reject",
                                                  "zero_weight")
        assert qos.stats()["a"]["rejected"] == 1

    def test_reject_mode_over_rate(self):
        clk = _VClock()
        qos, g = self._gate(clk, rate=1.0, burst=10.0,
                            on_limit="reject")
        assert g.decide(_FakeReq(tenant="a", cost=10))[0] == "admit"
        assert g.decide(_FakeReq(tenant="a", cost=10)) == (
            "reject", "rate_limited")
        assert qos.stats()["a"]["rejected"] == 1

    def test_throttle_release_fifo_no_queue_jump(self):
        clk = _VClock()
        qos, g = self._gate(clk, rate=10.0, burst=10.0)
        r1 = _FakeReq(tenant="a", cost=10, seq=1)
        r2 = _FakeReq(tenant="a", cost=10, seq=2)
        r3 = _FakeReq(tenant="a", cost=2, max_new=1, seq=3)
        assert g.decide(r1)[0] == "admit"
        assert g.decide(r2)[0] == "throttle"
        # r3 is tiny and WOULD fit the residual bucket — but a sibling
        # is already held: FIFO, no jumping
        assert g.decide(r3)[0] == "throttle"
        assert g.depth("a") == 2 and qos.gate_depth() == 2
        assert g.release() == []
        clk.t = 1.0                         # refill 10: funds r2 only
        assert g.release() == [r2]
        clk.t = 1.25
        assert g.release() == [r3]
        assert g.depth() == 0
        assert qos.stats()["a"]["throttled"] == 2

    def test_release_orders_across_tenants_by_arrival(self):
        clk = _VClock()
        qos = QoSPolicy([TenantPolicy("a", rate=10.0, burst=10.0),
                         TenantPolicy("b", rate=10.0, burst=10.0)],
                        clock=clk)
        g = qos.gate()
        # drain both buckets so the next decide() throttles
        assert qos.bucket("a").try_take(10)
        assert qos.bucket("b").try_take(10)
        rb = _FakeReq(tenant="b", cost=10, seq=5)
        ra = _FakeReq(tenant="a", cost=10, seq=9)
        assert g.decide(rb)[0] == "throttle"
        assert g.decide(ra)[0] == "throttle"
        clk.t = 1.0
        assert g.release() == [rb, ra]      # arrival order, not name

    def test_remove_drops_held_victims(self):
        clk = _VClock()
        qos, g = self._gate(clk, rate=1.0, burst=10.0)
        g.decide(_FakeReq(tenant="a", cost=10))
        victim = _FakeReq(tenant="a", cost=10)
        g.decide(victim)
        assert g.remove([victim]) == 1
        assert g.depth() == 0

    def test_gates_share_buckets_not_queues(self):
        """Two submit surfaces (engine + fleet) drain ONE bucket but
        hold their own throttled queues."""
        clk = _VClock()
        qos = QoSPolicy([TenantPolicy("a", rate=1.0, burst=10.0)],
                        clock=clk)
        g1, g2 = qos.gate(), qos.gate()
        assert g1.decide(_FakeReq(tenant="a", cost=10))[0] == "admit"
        assert g2.decide(_FakeReq(tenant="a", cost=1))[0] == "throttle"
        assert g1.depth() == 0 and g2.depth() == 1
        assert qos.gate_depth("a") == 2 - 1


# ---------------------------------------------------------------------------
# fair-share scheduler
# ---------------------------------------------------------------------------
class TestFairShareScheduler:
    def _qos(self, **weights):
        pols = [TenantPolicy(t, weight=w) for t, w in weights.items()]
        return QoSPolicy(pols, clock=_VClock())

    def test_single_tenant_matches_request_scheduler(self):
        """With one tenant the SFQ layer must reduce to the r7
        contract: priority desc, FCFS asc."""
        specs = [(0, None), (2, None), (0, None), (2, None), (1, None)]
        plain, fair = RequestScheduler(), FairShareScheduler(
            self._qos(a=1.0))
        reqs_p = [_FakeReq(priority=p) for p, _ in specs]
        reqs_f = [_FakeReq(tenant="a", priority=p) for p, _ in specs]
        for rp, rf in zip(reqs_p, reqs_f):
            plain.add(rp)
            fair.add(rf)
        order_p = [reqs_p.index(plain.pop()) for _ in range(len(specs))]
        order_f = [reqs_f.index(fair.pop()) for _ in range(len(specs))]
        assert order_p == order_f

    def test_weighted_service_ratio(self):
        """Both tenants backlogged, weights 3:1, equal request cost —
        served counts converge to the weight ratio."""
        qos = self._qos(a=3.0, b=1.0)
        s = FairShareScheduler(qos)
        for i in range(120):
            s.add(_FakeReq(tenant="a", cost=8))
            s.add(_FakeReq(tenant="b", cost=8))
        counts = {"a": 0, "b": 0}
        for _ in range(80):
            r = s.pop()
            t = tenant_of(r)
            counts[t] += 1
            s.charge(t, 8)
        assert counts["a"] == pytest.approx(60, abs=2)
        assert counts["b"] == pytest.approx(20, abs=2)

    def test_no_starvation_under_sustained_flood(self):
        """Property sim from the ISSUE: 10:1 weight skew, the heavy
        tenant floods continuously (a new arrival after every service),
        the light tenant has a finite queue — every light request is
        served within a bounded number of services, none starves."""
        qos = self._qos(heavy=10.0, light=1.0)
        s = FairShareScheduler(qos)
        light = [_FakeReq(tenant="light", cost=16) for _ in range(10)]
        for _ in range(50):
            s.add(_FakeReq(tenant="heavy", cost=16))
        for r in light:
            s.add(r)
        served_at = {}
        for step in range(400):
            r = s.pop()
            t = tenant_of(r)
            s.charge(t, 16)
            if t == "light":
                served_at[id(r)] = step
                if len(served_at) == len(light):
                    break
            s.add(_FakeReq(tenant="heavy", cost=16))   # sustain flood
        assert len(served_at) == len(light), "light tenant starved"
        # weight ratio 10:1 -> at most ~11 services between light pops
        gaps = sorted(served_at.values())
        assert gaps[0] <= 12
        assert all(b - a <= 13 for a, b in zip(gaps, gaps[1:])), gaps

    def test_idle_tenant_cannot_bank_credit(self):
        """A tenant that idles while another is served re-enters at the
        frontier — it does NOT get a monopoly for its idle time."""
        qos = self._qos(a=1.0, b=1.0)
        s = FairShareScheduler(qos)
        for _ in range(40):
            s.add(_FakeReq(tenant="a", cost=8))
        for _ in range(20):                 # b idle: a alone is served
            t = tenant_of(s.pop())
            assert t == "a"
            s.charge(t, 8)
        for _ in range(20):
            s.add(_FakeReq(tenant="b", cost=8))
        run_b = 0
        for _ in range(10):                 # b re-enters at frontier:
            t = tenant_of(s.pop())          # alternation, not monopoly
            s.charge(t, 8)
            run_b += (t == "b")
        assert run_b <= 6

    def test_peek_pop_coherent_across_add_and_charge(self):
        """The engine peeks, may preempt (re-add victims + charge the
        claimant), then pops — pop must remove exactly the peeked
        request even after the interleaved mutation."""
        qos = self._qos(a=1.0, b=1.0)
        s = FairShareScheduler(qos)
        claimant = _FakeReq(tenant="a", cost=8, priority=1)
        s.add(claimant)
        assert s.peek() is claimant
        victim = _FakeReq(tenant="a", cost=8, priority=2)
        s.add(victim)                       # re-queued preemption victim
        s.charge("a", 64)                   # claimant pays eviction
        assert s.pop() is claimant          # NOT the higher-prio victim
        assert s.pop() is victim

    def test_remove_and_requests_views(self):
        qos = self._qos(a=1.0, b=1.0)
        s = FairShareScheduler(qos)
        reqs = [_FakeReq(tenant=t, cost=8) for t in ("a", "b", "a")]
        for r in reqs:
            s.add(r)
        assert set(map(id, s.requests())) == set(map(id, reqs))
        assert s.remove([reqs[0], reqs[1]]) == 2
        assert len(s) == 1 and s.pop() is reqs[2]

    def test_add_marks_trace_queued(self):
        s = FairShareScheduler(self._qos(a=1.0))
        r = _FakeReq(tenant="a")
        s.add(r)
        assert r.trace.count("queued") == 1


# ---------------------------------------------------------------------------
# shed planning
# ---------------------------------------------------------------------------
class TestShedPlan:
    def _qos(self):
        return QoSPolicy([
            TenantPolicy("bulk", tier=0, shed_floor=1),
            TenantPolicy("vip", tier=5, shed_floor=2),
        ], clock=_VClock())

    def test_lowest_tier_newest_first(self):
        qos = self._qos()
        bulk = [_FakeReq(tenant="bulk", seq=i) for i in range(4)]
        vip = [_FakeReq(tenant="vip", seq=10 + i) for i in range(3)]
        victims = qos.shed_plan(bulk + vip, target=4)
        # 3 victims: all bulk (tier 0), newest (highest seq) first
        assert [id(v) for v in victims] == [id(bulk[3]), id(bulk[2]),
                                            id(bulk[1])]

    def test_floor_counts_running_rows(self):
        qos = self._qos()
        bulk = [_FakeReq(tenant="bulk", seq=i) for i in range(3)]
        # no running rows: floor 1 keeps one bulk pending
        assert len(qos.shed_plan(bulk, target=0)) == 2
        # a running bulk row already satisfies the floor: shed all 3
        assert len(qos.shed_plan(bulk, {"bulk": 1}, target=0)) == 3

    def test_vip_floor_protects_under_total_shed(self):
        qos = self._qos()
        vip = [_FakeReq(tenant="vip", seq=i) for i in range(4)]
        victims = qos.shed_plan(vip, target=0)
        assert len(victims) == 2            # floor 2 retained

    def test_no_excess_no_victims(self):
        qos = self._qos()
        reqs = [_FakeReq(tenant="bulk", seq=i) for i in range(3)]
        assert qos.shed_plan(reqs, target=3) == []
        assert qos.shed_plan([], target=0) == []


# ---------------------------------------------------------------------------
# submit-path validation (satellite a)
# ---------------------------------------------------------------------------


class TestSubmitValidation:
    def test_request_ctor_validates(self):
        from paddle_tpu.inference.serving import _Request
        with pytest.raises(ValueError, match="empty"):
            _Request(np.array([], np.int32), 4)
        with pytest.raises(ValueError, match="positive"):
            _Request(np.array([1, 2], np.int32), 0)
        with pytest.raises(ValueError, match="positive"):
            _Request(np.array([1, 2], np.int32), -3)

    def test_engine_submit_validates(self):
        from paddle_tpu.inference.serving import DecodeEngine
        eng = DecodeEngine(shared_model(), capacity=2, s_max=64, chunk=4)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.array([], np.int32))
        with pytest.raises(ValueError, match="positive"):
            eng.submit(np.array([1, 2], np.int32), max_new_tokens=0)

    def test_batching_server_submit_validates(self):
        from paddle_tpu.inference.serving import (BatchingServer,
                                                  GenerationPredictor)
        srv = BatchingServer(GenerationPredictor(shared_model()))
        try:
            with pytest.raises(ValueError, match="empty"):
                srv.submit(np.array([], np.int32))
            with pytest.raises(ValueError, match="positive"):
                # explicit 0 must NOT fall through to the default
                srv.submit(np.array([1, 2], np.int32),
                           max_new_tokens=0)
        finally:
            srv.close()

    def test_fleet_submit_validates(self):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet(shared_model(), n_workers=2,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8))
        try:
            with pytest.raises(ValueError, match="empty"):
                fleet.submit(np.array([], np.int32))
            with pytest.raises(ValueError, match="positive"):
                fleet.submit(np.array([1, 2], np.int32),
                             max_new_tokens=0)
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# engine + QoS integration
# ---------------------------------------------------------------------------
class TestEngineQoS:
    def test_qos_requires_paged(self):
        from paddle_tpu.inference.serving import DecodeEngine
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(shared_model(), paged=False,
                         qos=QoSPolicy(clock=_VClock()))

    def test_submit_requires_paged(self):
        from paddle_tpu.inference.serving import DecodeEngine
        eng = DecodeEngine(shared_model(), paged=False)
        with pytest.raises(RuntimeError, match="paged"):
            eng.submit(np.array([1, 2], np.int32))

    def test_outputs_bit_identical_with_unlimited_qos(self):
        """Acceptance (c) flip side: an unlimited single-tenant QoS
        config must not perturb the decode — outputs stay bit-identical
        to the qos-less engine over the same workload."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (7, 5, 9, 4)]
        plain = DecodeEngine(m, capacity=2, s_max=64, chunk=4)
        pend = [_Request(p, 6) for p in prompts]
        plain_reqs = list(pend)
        pending = list(pend)
        for _ in range(300):
            plain.admit(pending)
            plain.decode_once()
            if plain.idle() and not pending:
                break
        qos = QoSPolicy(clock=_VClock())
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4, qos=qos)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        drive(eng)
        for rq, rp in zip(reqs, plain_reqs):
            np.testing.assert_array_equal(rq.wait(timeout=1),
                                          rp.wait(timeout=1))

    def test_submit_reject_fails_fast_with_reason(self):
        from paddle_tpu.inference.serving import DecodeEngine
        qos = QoSPolicy([TenantPolicy("free", weight=0.0)],
                        clock=_VClock())
        eng = DecodeEngine(shared_model(), capacity=2, s_max=64, chunk=4,
                           qos=qos)
        req = eng.submit(np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=4, tenant="free")
        with pytest.raises(PermissionError, match="zero_weight"):
            req.wait(timeout=1)
        assert req.trace.attrs["reject_reason"] == "zero_weight"
        assert req.trace.terminal == "failed"

    def test_submit_throttle_releases_on_refill(self):
        """Clock-injected end-to-end: the second request sits behind
        the bucket until the virtual clock refills it, then retires
        with solo-parity tokens."""
        from paddle_tpu.inference.serving import DecodeEngine
        m = shared_model()
        clk = _VClock()
        p = np.arange(1, 7, dtype=np.int32)          # cost 6 + 4 = 10
        qos = QoSPolicy([TenantPolicy("a", rate=10.0, burst=10.0)],
                        clock=clk)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4, qos=qos)
        r1 = eng.submit(p, max_new_tokens=4, tenant="a")
        r2 = eng.submit(p, max_new_tokens=4, tenant="a")
        assert eng._qos_gate.depth() == 1            # r2 held
        drive(eng)
        assert r1.wait(timeout=1) is not None
        assert not r2.event.is_set()                 # still gated
        clk.t = 1.0                                  # refill 10 tokens
        drive(eng)
        ref = solo_generate(m, p, 4)
        np.testing.assert_array_equal(r2.wait(timeout=1), ref)
        assert qos.stats()["a"]["throttled"] == 1
        assert qos.stats()["a"]["admitted"] == 2
        # gate wait is queue wait: the trace saw ONE queued->admitted
        # stint spanning the throttle
        assert r2.trace.queue_wait > 0.0

    def test_two_tenant_engine_drains_with_parity(self):
        """Fair sharing reorders service between tenants but never
        corrupts it — every request still bit-matches solo decode."""
        from paddle_tpu.inference.serving import DecodeEngine
        m = shared_model()
        rng = np.random.RandomState(7)
        qos = QoSPolicy([TenantPolicy("h", weight=1.0),
                         TenantPolicy("l", weight=10.0)],
                        clock=_VClock())
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4, qos=qos)
        work = []
        for i in range(6):
            p = rng.randint(1, 128, (4 + i,)).astype(np.int32)
            work.append((p, eng.submit(p, max_new_tokens=5,
                                       tenant="h" if i % 3 else "l")))
        drive(eng)
        for p, r in work:
            np.testing.assert_array_equal(r.wait(timeout=1),
                                          solo_generate(m, p, 5))
        st = qos.stats()
        assert st["h"]["served_tokens"] == 4 * 5
        assert st["l"]["served_tokens"] == 2 * 5


# ---------------------------------------------------------------------------
# fleet end-to-end: SLO-driven shedding
# ---------------------------------------------------------------------------
class TestFleetShedding:
    def test_shed_requires_qos(self):
        from paddle_tpu.inference.fleet import ServingFleet
        fleet = ServingFleet(shared_model(), n_workers=1,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8))
        try:
            with pytest.raises(ValueError, match="qos"):
                fleet.enable_slo(shed=True)
        finally:
            fleet.close()

    def test_burn_rate_shed_end_to_end(self):
        """Flood a 1-worker fleet past a backlog SLO on a virtual
        clock: every shed victim fails LOUDLY (RequestShedError,
        ``shed_reason`` on the trace, counter increment), the
        shed-protected vip tenant fully retires, and every survivor
        bit-matches solo decode."""
        from paddle_tpu.inference.fleet import ServingFleet
        from paddle_tpu.observability import SLORule
        m = shared_model()
        clk = _VClock()
        qos = QoSPolicy([
            TenantPolicy("bulk", tier=0, shed_floor=1),
            TenantPolicy("vip", tier=1, shed_floor=1),
        ], clock=clk)
        fleet = ServingFleet(m, n_workers=1,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8),
                             qos=qos)
        fleet.enable_slo(rules=[
            SLORule("backlog", "engine_backlog", "value",
                    threshold=2.0, window_s=60.0)],
            shed=True, shed_target_backlog=2)
        rng = np.random.RandomState(11)
        work = []
        for i in range(10):
            p = rng.randint(1, 128, (5,)).astype(np.int32)
            work.append((p, fleet.submit(p, max_new_tokens=4,
                                         tenant="bulk")))
        vip_p = rng.randint(1, 128, (6,)).astype(np.int32)
        vip = fleet.submit(vip_p, max_new_tokens=4, tenant="vip")
        work.append((vip_p, vip))
        for _ in range(200):
            fleet.step()
            fleet.check_slo(now=clk.t)
            clk.t += 0.25
            if not fleet.pending_work():
                break
        assert not fleet.pending_work()
        shed, retired = [], []
        for p, r in work:
            if r.trace.terminal == "failed":
                shed.append(r)
                with pytest.raises(RequestShedError,
                                   match="slo_burn_rate:backlog"):
                    r.wait(timeout=1)
                assert r.trace.attrs["shed_reason"].startswith(
                    "slo_burn_rate:")
            else:
                retired.append((p, r))
        assert shed, "overload never triggered shedding"
        st = fleet.stats()
        assert st["shed"] == len(shed)
        assert sum(t["shed"] for t in st["qos"].values()) == len(shed)
        # the shed-protected tier survived
        assert vip.trace.terminal == "retired"
        assert st["qos"]["vip"]["shed"] == 0
        # loud, not lossy: survivors still bit-match solo decode
        for p, r in retired:
            np.testing.assert_array_equal(r.wait(timeout=1),
                                          solo_generate(m, p, 4))
        fleet.close()

    def test_fleet_reject_tenant(self):
        from paddle_tpu.inference.fleet import ServingFleet
        qos = QoSPolicy([TenantPolicy("m", rate=1.0, burst=1.0,
                                      on_limit="reject")],
                        clock=_VClock())
        fleet = ServingFleet(shared_model(), n_workers=1,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8),
                             qos=qos)
        try:
            req = fleet.submit(np.arange(1, 6, dtype=np.int32),
                               max_new_tokens=4, tenant="m")
            with pytest.raises(PermissionError, match="rate_limited"):
                req.wait(timeout=1)
            assert req.trace.attrs["reject_reason"] == "rate_limited"
            assert fleet.stats()["qos_rejected"] == 1
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# tenant-labeled telemetry (satellites b, f)
# ---------------------------------------------------------------------------
class TestTenantTelemetry:
    def test_trace_summary_appends_tenant_after_attrs(self):
        tr = RequestTrace(tenant="t3")
        s = tr.summary()
        keys = list(s)
        assert s["tenant"] == "t3"
        assert keys.index("tenant") > keys.index("attrs")
        assert RequestTrace().summary()["tenant"] is None

    def test_chrome_export_carries_tenant(self):
        tr = RequestTrace(tenant="t3")
        tr.mark("queued", t=tr.arrival + 0.1)
        evs = tr.to_events()
        assert all(e["args"]["tenant"] == "t3" for e in evs)
        # no tenant -> byte-identical r10 args (no key at all)
        evs0 = RequestTrace().to_events()
        assert all("tenant" not in e["args"] for e in evs0)

    def test_aggregator_tenant_labels_beside_workers(self):
        from paddle_tpu.inference.fleet_metrics import MetricsAggregator
        from paddle_tpu.observability import MetricsRegistry
        agg = MetricsAggregator()
        wr = MetricsRegistry()
        wr.counter("engine_retired_total", "t").inc(5)
        agg.add("w0", wr)
        tr = MetricsRegistry()
        tr.counter("qos_shed_total", "t").inc(3)
        agg.add_labels({"tenant": "t3"}, tr)
        text = agg.prometheus_text()
        assert 'engine_retired_total{worker="w0"} 5' in text
        assert 'qos_shed_total{tenant="t3"} 3' in text
        snap = agg.snapshot()
        assert snap["workers"]["tenant=t3"]["counters"][
            "qos_shed_total"] == 3
        # tenant entries are EXCLUDED from the fleet merge (they
        # partition the same events the workers already count)
        assert "qos_shed_total" not in snap["fleet"]["counters"]
        assert snap["fleet"]["counters"]["engine_retired_total"] == 5

    def test_aggregator_duplicate_and_empty_labels_raise(self):
        from paddle_tpu.inference.fleet_metrics import MetricsAggregator
        from paddle_tpu.observability import MetricsRegistry
        agg = MetricsAggregator()
        agg.add_labels({"tenant": "a"}, MetricsRegistry())
        with pytest.raises(ValueError, match="duplicate"):
            agg.add_labels({"tenant": "a"}, MetricsRegistry())
        with pytest.raises(ValueError, match="label"):
            agg.add_labels({}, MetricsRegistry())

    def test_aggregator_type_conflict_across_label_sets(self):
        from paddle_tpu.inference.fleet_metrics import MetricsAggregator
        from paddle_tpu.observability import MetricsRegistry
        agg = MetricsAggregator()
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.counter("x_total", "h")
        r2.gauge("x_total", "h")
        agg.add("w0", r1)
        agg.add_labels({"tenant": "t"}, r2)
        with pytest.raises(TypeError, match="conflicting"):
            agg.prometheus_text()

    def test_tenant_label_escaping(self):
        from paddle_tpu.inference.fleet_metrics import MetricsAggregator
        from paddle_tpu.observability import MetricsRegistry
        agg = MetricsAggregator()
        reg = MetricsRegistry()
        reg.counter("qos_shed_total", "t").inc()
        agg.add_labels({"tenant": 'we"ird\\te\nnant'}, reg)
        text = agg.prometheus_text()
        assert 'tenant="we\\"ird\\\\te\\nnant"' in text

    def test_fleet_aggregator_includes_tenant_registries(self):
        from paddle_tpu.inference.fleet import ServingFleet
        qos = QoSPolicy(clock=_VClock())
        fleet = ServingFleet(shared_model(), n_workers=1,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8),
                             qos=qos)
        try:
            req = fleet.submit(np.arange(1, 6, dtype=np.int32),
                               max_new_tokens=4, tenant="t3")
            while fleet.pending_work():
                fleet.step()
            req.wait(timeout=1)
            agg = fleet.aggregator()
            assert "tenant=t3" in agg.labels()
            text = agg.prometheus_text()
            assert 'qos_admitted_total{tenant="t3"} 1' in text
            assert 'qos_served_tokens_total{tenant="t3"} 4' in text
        finally:
            fleet.close()


# ---------------------------------------------------------------------------
# traffic generator
# ---------------------------------------------------------------------------
class TestTraffic:
    _TENANTS = [TenantProfile("h", share=10.0),
                TenantProfile("l", share=1.0)]

    def test_same_seed_same_arrivals(self):
        a = TrafficGenerator(self._TENANTS, rate=5.0,
                             seed=42).arrivals(20.0)
        b = TrafficGenerator(self._TENANTS, rate=5.0,
                             seed=42).arrivals(20.0)
        assert a == b and len(a) > 10
        c = TrafficGenerator(self._TENANTS, rate=5.0,
                             seed=43).arrivals(20.0)
        assert a != c

    def test_validation(self):
        with pytest.raises(ValueError, match="process"):
            TrafficGenerator(self._TENANTS, process="lumpy")
        with pytest.raises(ValueError, match="prompt_dist"):
            TrafficGenerator(self._TENANTS, prompt_dist="zipf")
        with pytest.raises(ValueError, match="rate"):
            TrafficGenerator(self._TENANTS, rate=0.0)
        with pytest.raises(ValueError, match="prompt_min"):
            TrafficGenerator(self._TENANTS, prompt_min=9, prompt_max=4)
        with pytest.raises(ValueError):
            TrafficGenerator([])
        with pytest.raises(ValueError, match="share"):
            TenantProfile("x", share=0.0)

    @pytest.mark.parametrize("process", ["constant", "poisson",
                                         "bursty", "diurnal"])
    def test_processes_sorted_and_bounded(self, process):
        arr = TrafficGenerator(self._TENANTS, rate=8.0, seed=1,
                               process=process).arrivals(10.0)
        ts = [r.t for r in arr]
        assert ts == sorted(ts)
        assert all(0.0 < t < 10.0 for t in ts)
        assert len(arr) > 0

    def test_tenant_skew_follows_shares(self):
        arr = TrafficGenerator(self._TENANTS, rate=50.0, seed=0,
                               process="poisson").arrivals(40.0)
        n_h = sum(r.tenant == "h" for r in arr)
        assert n_h / len(arr) == pytest.approx(10 / 11, abs=0.05)

    def test_prompt_lengths_bounded_heavy_tail(self):
        gen = TrafficGenerator(self._TENANTS, rate=50.0, seed=0,
                               prompt_min=4, prompt_max=32)
        arr = gen.arrivals(30.0)
        lens = [r.prompt_len for r in arr]
        assert all(4 <= n <= 32 for n in lens)
        assert min(lens) < 8 < max(lens)    # short mode, fat tail

    def test_prompt_ids_deterministic_and_in_vocab(self):
        gen = TrafficGenerator(self._TENANTS, rate=5.0, seed=0)
        arr = gen.arrivals(10.0)
        a = gen.prompt_ids(arr[0], 512, index=0)
        b = gen.prompt_ids(arr[0], 512, index=0)
        np.testing.assert_array_equal(a, b)
        assert a.size == arr[0].prompt_len
        assert a.min() >= 1 and a.max() < 512
        c = gen.prompt_ids(arr[0], 512, index=1)
        assert not np.array_equal(a, c)

    def test_jain_index(self):
        assert jain_index([]) == 1.0
        assert jain_index([0, 0]) == 1.0
        assert jain_index([5, 5, 5]) == pytest.approx(1.0)
        assert jain_index([1, 0, 0, 0]) == pytest.approx(0.25)
        assert jain_index([10, 1]) == pytest.approx(121 / 202)

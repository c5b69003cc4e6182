"""ISSUE 5 acceptance: propagated request traces (ONE trace spanning a
failover, with per-worker Chrome lanes), streaming SLO evaluation
(deterministic pending -> firing -> resolved via injected ``now=``,
wired into the fleet's router load penalty), and the resilient
telemetry shipper (always-raising sink drops with backoff, serving
output stays bit-identical)."""

import json

import numpy as np
import pytest

from paddle_tpu.inference.fleet import ServingFleet
from paddle_tpu.inference.fleet_metrics import MetricsAggregator
from paddle_tpu.observability import (MetricsRegistry, RequestTrace,
                                      SLOEngine, SLORule,
                                      TelemetryShipper, merge_snapshots)

from harness import ENGINE_KW, shared_model, solo_generate


# ---------------------------------------------------------------------------
# RequestTrace propagation (tentpole part 1)
# ---------------------------------------------------------------------------
class TestTracePropagation:
    def test_trace_ids_are_unique_and_overridable(self):
        a, b = RequestTrace(t=0.0), RequestTrace(t=0.0)
        assert a.trace_id != b.trace_id
        c = RequestTrace(t=0.0, trace_id="abc")
        assert c.trace_id == "abc"

    def test_summary_keeps_r8_keys_and_appends_fleet_keys(self):
        tr = RequestTrace(request_id=3, t=0.0)
        tr.mark("admitted", t=1.0, worker="w0")
        tr.mark("first_token", t=2.0, worker="w0")
        tr.mark("retired", t=3.0, worker="w0")
        s = tr.summary()
        # r8 consumers' keys, unchanged
        for key in ("request_id", "state", "ttft_s", "queue_wait_s",
                    "preemptions", "decode_chunks", "events"):
            assert key in s
        assert s["state"] == "retired" and s["ttft_s"] == 2.0
        # fleet keys appended
        assert s["trace_id"] == tr.trace_id
        assert s["worker_id"] is None           # no attrs set explicitly
        assert s["hops"] == [] and s["attrs"] == {}
        json.dumps(s)                           # JSON-able

    def test_hop_splits_worker_residency(self):
        tr = RequestTrace(request_id=7, t=0.0)
        tr.mark("queued", t=1.0)
        tr.mark("admitted", t=2.0, worker="w0")
        tr.mark("decode_chunk", t=3.0, worker="w0")
        tr.add_hop("w0", "w1", reason="killed", t=4.0)
        tr.mark("admitted", t=5.0, worker="w1")
        tr.mark("first_token", t=5.5, worker="w1")
        tr.mark("retired", t=6.0, worker="w1")
        # the hop CUTS the w0 span at t=4 even though no w1 event
        # existed yet at that instant
        assert tr._segments() == [("w0", 2.0, 4.0), ("w1", 4.0, 6.0)]
        assert tr.workers == ["w0", "w1"]
        assert tr.attrs["worker_id"] == "w1"
        assert tr.hops == [{"t": 4.0, "from": "w0", "to": "w1",
                            "reason": "killed"}]

    def test_to_events_lanes_and_hop_instant(self):
        pids = {"w0": 1, "w1": 2}
        tr = RequestTrace(request_id=7, t=0.0)
        tr.mark("admitted", t=2.0, worker="w0")
        tr.add_hop("w0", "w1", reason="killed", t=4.0)
        tr.mark("retired", t=6.0, worker="w1")
        ev = tr.to_events(pid_for=lambda w: pids.get(w, 0))
        spans = {e["name"]: e for e in ev if e["ph"] == "X"}
        assert spans["req7@w0"]["pid"] == 1
        assert spans["req7@w0"]["ts"] == 2.0e6
        assert spans["req7@w0"]["dur"] == 2.0e6
        assert spans["req7@w1"]["pid"] == 2
        hop, = [e for e in ev if e["name"] == "req7.hop"]
        assert hop["ph"] == "i" and hop["pid"] == 2
        assert hop["args"]["from"] == "w0"
        assert hop["args"]["reason"] == "killed"
        assert hop["args"]["trace_id"] == tr.trace_id
        # instants carry the pid forward: arrival is router-lane (0),
        # post-admission marks ride the owning worker's lane
        inst = {e["name"]: e["pid"] for e in ev if e["ph"] == "i"}
        assert inst["req7.arrival"] == 0
        assert inst["req7.admitted"] == 1
        assert inst["req7.retired"] == 2
        assert all(e["args"]["trace_id"] == tr.trace_id for e in ev)


# ---------------------------------------------------------------------------
# SLO engine unit semantics (tentpole part 2)
# ---------------------------------------------------------------------------
class TestSLORuleValidation:
    def test_bad_stat_op_and_ratio_without_total_raise(self):
        with pytest.raises(ValueError, match="unknown stat"):
            SLORule("x", "m", "p77", threshold=1.0)
        with pytest.raises(ValueError, match="unknown op"):
            SLORule("x", "m", "p99", threshold=1.0, op="!=")
        with pytest.raises(ValueError, match="total"):
            SLORule("x", "m", "ratio", threshold=0.1)

    def test_holds_ops(self):
        assert SLORule("a", "m", "p99", threshold=1.0).holds(0.5)
        assert not SLORule("a", "m", "p99", threshold=1.0).holds(1.0)
        assert SLORule("a", "m", "p99", threshold=1.0,
                       op="<=").holds(1.0)
        assert SLORule("a", "m", "rate", threshold=1.0,
                       op=">").holds(2.0)

    def test_duplicate_rule_names_raise(self):
        r = SLORule("a", "m", "p99", threshold=1.0)
        with pytest.raises(ValueError, match="duplicate"):
            SLOEngine([r, r])


class TestSLOStateMachine:
    def _ttft_engine(self, **kw):
        reg = MetricsRegistry()
        h = reg.histogram("ttft", "time to first token")
        rule = SLORule("ttft_p99", "ttft", "p99", threshold=0.5,
                       window_s=30.0, **kw)
        return reg, h, SLOEngine([rule])

    def test_pending_firing_resolved_is_deterministic(self):
        reg, h, eng = self._ttft_engine(for_s=5.0, clear_for_s=10.0)
        for _ in range(100):
            h.observe(0.01)                     # healthy traffic
        eng.observe(reg.snapshot(), now_=0.0)
        assert eng.check(now_=0.0) == []
        assert eng.states() == {"ttft_p99": "ok"}

        for _ in range(100):
            h.observe(1.0)                      # injected regression
        eng.observe(reg.snapshot(), now_=10.0)
        assert eng.check(now_=10.0) == []       # breach held, not fired
        assert eng.states() == {"ttft_p99": "pending"}

        ev = eng.check(now_=15.0)               # held >= for_s -> fires
        assert [e["state"] for e in ev] == ["firing"]
        assert ev[0]["rule"] == "ttft_p99"
        assert ev[0]["measured"] > 0.5
        # half the windowed observations breach a p99 objective: the
        # error budget (1%) burns at 0.5 / 0.01 = 50x
        assert ev[0]["burn_rate"] == pytest.approx(50.0)
        assert eng.alert("ttft_p99").fired_count == 1
        assert eng.firing() == ["ttft_p99"]

        # regression ends: cumulative counters stop moving, the window
        # slides past the bad stretch -> no data -> objective met
        eng.observe(reg.snapshot(), now_=50.0)
        assert eng.check(now_=50.0) == []       # hysteresis hold
        assert eng.states() == {"ttft_p99": "firing"}
        ev = eng.check(now_=61.0)               # clear held >= clear_for_s
        assert [e["state"] for e in ev] == ["resolved"]
        assert eng.states() == {"ttft_p99": "ok"}
        assert [e["state"] for e in eng.transitions] == ["firing",
                                                         "resolved"]

    def test_for_s_zero_fires_on_first_breaching_check(self):
        reg, h, eng = self._ttft_engine(for_s=0.0)
        for _ in range(10):
            h.observe(1.0)
        eng.observe(reg.snapshot(), now_=0.0)
        ev = eng.check(now_=0.0)
        assert [e["state"] for e in ev] == ["firing"]

    def test_pending_clears_without_firing(self):
        reg, h, eng = self._ttft_engine(for_s=5.0)
        for _ in range(10):
            h.observe(1.0)
        eng.observe(reg.snapshot(), now_=0.0)
        eng.check(now_=0.0)
        assert eng.states() == {"ttft_p99": "pending"}
        eng.observe(reg.snapshot(), now_=40.0)  # breach slid out before
        eng.check(now_=40.0)                    # the for_s hold elapsed
        assert eng.states() == {"ttft_p99": "ok"}
        assert eng.transitions == []

    def test_ratio_rule_is_windowed(self):
        reg = MetricsRegistry()
        failed = reg.counter("failed")
        retired = reg.counter("retired")
        eng = SLOEngine([SLORule(
            "err", "failed", "ratio", threshold=0.1, window_s=30.0,
            total=("retired", "failed"))])
        retired.inc(100)
        failed.inc(1)
        eng.observe(reg.snapshot(), now_=0.0)
        assert eng.check(now_=0.0) == []        # 1/101 < 10%
        failed.inc(50)                          # failure spike
        eng.observe(reg.snapshot(), now_=10.0)
        ev = eng.check(now_=10.0)
        assert [e["state"] for e in ev] == ["firing"]
        assert ev[0]["measured"] == pytest.approx(51 / 151)
        # the spike slides out of the window: delta counters are zero,
        # no-data means the objective is met again
        eng.observe(reg.snapshot(), now_=45.0)
        ev = eng.check(now_=45.0)
        assert [e["state"] for e in ev] == ["resolved"]

    def test_no_data_is_objective_met(self):
        _, _, eng = self._ttft_engine()
        assert eng.check(now_=0.0) == []
        assert eng.states() == {"ttft_p99": "ok"}

    def test_on_alert_exceptions_are_contained(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft")
        calls = []

        def hook(info):
            calls.append(info)
            raise RuntimeError("pager down")

        eng = SLOEngine([SLORule("ttft_p99", "ttft", "p99",
                                 threshold=0.5)], on_alert=hook)
        h.observe(1.0)
        eng.observe(reg.snapshot(), now_=0.0)
        ev = eng.check(now_=0.0)                # must not raise
        assert len(ev) == len(calls) == 1
        assert eng.transitions == ev            # still recorded

    def test_engine_self_observes_into_registry(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft")
        meta = MetricsRegistry()
        eng = SLOEngine([SLORule("ttft_p99", "ttft", "p99",
                                 threshold=0.5)], registry=meta)
        h.observe(1.0)
        eng.observe(reg.snapshot(), now_=0.0)
        eng.check(now_=0.0)
        snap = meta.snapshot()
        assert snap["counters"]["slo_alerts_fired_total"] == 1
        assert snap["gauges"]["slo_alerts_firing"] == 1
        eng.observe(reg.snapshot(), now_=100.0)  # past the 60s window
        eng.check(now_=100.0)
        snap = meta.snapshot()
        assert snap["counters"]["slo_alerts_resolved_total"] == 1
        assert snap["gauges"]["slo_alerts_firing"] == 0


# ---------------------------------------------------------------------------
# Telemetry shipper unit semantics (tentpole part 3)
# ---------------------------------------------------------------------------
class _BoomSink:
    def __init__(self):
        self.calls = 0

    def emit(self, payload):
        self.calls += 1
        raise OSError("collector unreachable")


class _FlakySink:
    def __init__(self, fail_first):
        self.fail_first = fail_first
        self.out = []

    def emit(self, payload):
        if self.fail_first > 0:
            self.fail_first -= 1
            raise OSError("transient")
        self.out.append(payload)


class TestTelemetryShipper:
    def test_raising_sink_backs_off_and_drops_oldest(self):
        ship = TelemetryShipper(collect=lambda: {"n": 1},
                                sinks=[_BoomSink()], interval_s=1.0,
                                queue_max=3, backoff_base_s=0.5,
                                backoff_max_s=4.0, jitter=0.0)
        ship.flush(now_=0.0)                    # first failure
        st = ship.stats()
        assert st["sink_errors"] == 1 and st["retries"] == 0
        assert ship._sinks[0].backoff_s == 0.5
        ship.flush(now_=0.25)                   # inside backoff: enqueue
        assert ship.stats()["sink_errors"] == 1  # only, no emit attempt
        assert ship.stats()["queue_depth"] == 2
        ship.flush(now_=0.5)                    # retry -> fail -> double
        assert ship._sinks[0].backoff_s == 1.0
        ship.flush(now_=1.5)
        ship.flush(now_=3.5)
        ship.flush(now_=7.5)                    # 2.0 -> 4.0 -> capped
        st = ship.stats()
        assert ship._sinks[0].backoff_s == 4.0  # == backoff_max_s
        assert st["sink_errors"] == 5 and st["retries"] == 4
        assert st["queue_depth"] == 3           # bounded
        assert st["dropped"] == 3               # drop-OLDEST, counted
        assert st["shipped"] == 0
        snap = ship.registry.snapshot()         # self-observation
        assert snap["counters"]["shipper_dropped_total"] == 3
        assert snap["gauges"]["shipper_queue_depth"] == 3
        assert snap["gauges"]["shipper_backoff_seconds"] == 4.0

    def test_recovery_drains_queue_in_order(self):
        sink = _FlakySink(fail_first=2)
        seq = iter(range(100))
        ship = TelemetryShipper(collect=lambda: {"n": next(seq)},
                                sinks=[sink], interval_s=1.0,
                                queue_max=8, backoff_base_s=0.5,
                                jitter=0.0)
        ship.flush(now_=0.0)
        ship.flush(now_=0.5)
        assert ship.stats()["shipped"] == 0
        delivered = ship.flush(now_=1.5)        # sink recovered
        assert delivered == 3
        assert [p["n"] for p in sink.out] == [0, 1, 2]  # order kept
        st = ship.stats()
        assert st["shipped"] == 3 and st["queue_depth"] == 0
        assert ship._sinks[0].backoff_s == 0.0  # reset on success

    def test_tick_honors_interval(self):
        sink = _FlakySink(fail_first=0)
        ship = TelemetryShipper(collect=lambda: {"n": 1}, sinks=[sink],
                                interval_s=1.0)
        assert ship.tick(now_=0.0) == 1         # first tick flushes
        assert ship.tick(now_=0.5) == 0         # interval not elapsed
        assert ship.tick(now_=1.0) == 1
        assert ship.stats()["enqueued"] == 2

    def test_collect_exception_is_contained(self):
        def boom():
            raise RuntimeError("registry exploded")

        sink = _FlakySink(fail_first=0)
        ship = TelemetryShipper(collect=boom, sinks=[sink])
        assert ship.flush(now_=0.0) == 0        # no raise, no payload
        assert ship.stats()["enqueued"] == 0

    def test_jitter_is_seeded_and_deterministic(self):
        def run(seed):
            ship = TelemetryShipper(collect=lambda: {"n": 1},
                                    sinks=[_BoomSink()], jitter=0.5,
                                    seed=seed, backoff_base_s=0.5)
            ship.flush(now_=0.0)
            ship.flush(now_=100.0)
            return ship._sinks[0].backoff_s

        assert run(7) == run(7)                 # replayable
        a, b = run(1), run(2)
        assert a != b                           # but genuinely jittered


# ---------------------------------------------------------------------------
# Fleet integration: one trace across failover + Chrome lanes
# ---------------------------------------------------------------------------
class TestFleetTraceFailover:
    def test_one_trace_spans_killed_worker(self, tmp_path):
        """The acceptance bar: kill a worker mid-flight; each re-routed
        request keeps ONE trace (same trace_id) whose hop links the
        dead worker's segment to the survivor's, the Chrome export puts
        the segments in per-worker lanes, and output still bit-matches
        solo."""
        m = shared_model()
        rng = np.random.RandomState(5)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        reqs, expect = [], []
        for _ in range(4):
            p = rng.randint(1, 128, (10,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=16))
            expect.append(solo_generate(m, p, 16))
        ids_before = [r.trace.trace_id for r in reqs]
        fleet.step()
        assert fleet.workers[1].occupancy > 0
        moved = fleet.kill_worker("w1")
        assert moved > 0
        fleet.run_until_drained()
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=60)).reshape(-1),
                e.reshape(-1))
        # trace identity survived the failover — no new trace was cut
        assert [r.trace.trace_id for r in reqs] == ids_before
        hopped = [r.trace for r in reqs if r.trace.hops]
        assert len(hopped) == moved
        for tr in hopped:
            assert len(tr.hops) == 1
            hop = tr.hops[0]
            assert hop["from"] == "w1" and hop["to"] == "w0"
            assert hop["reason"] == "killed"
            assert tr.workers == ["w1", "w0"]   # first-touch order
            assert tr.attrs["worker_id"] == "w0"
            assert tr.terminal == "retired" and tr.is_complete()
            s = tr.summary()
            assert s["trace_id"] == tr.trace_id
            assert s["hops"] == tr.hops
        untouched = [r.trace for r in reqs if not r.trace.hops]
        assert all(tr.workers == ["w0"] for tr in untouched)
        # every submit stamped the router span
        assert all(r.trace.attrs["route_reason"] == "round_robin"
                   for r in reqs)

        path = tmp_path / "fleet_timeline.json"
        assert fleet.export_chrome_timeline(str(path)) == str(path)
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        lanes = {e["pid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M"}
        assert lanes == {0: "router", 1: "worker w0", 2: "worker w1"}
        tr = hopped[0]
        spans = [e for e in evs if e["ph"] == "X"
                 and e["args"].get("trace_id") == tr.trace_id]
        assert {(e["args"]["worker"], e["pid"]) for e in spans} == \
            {("w1", 2), ("w0", 1)}              # one lane per worker
        hop_ev, = [e for e in evs if e["name"].endswith(".hop")
                   and e["args"]["trace_id"] == tr.trace_id]
        assert hop_ev["pid"] == 1               # instant on the TARGET
        assert hop_ev["args"]["reason"] == "killed"
        fleet.close()


# ---------------------------------------------------------------------------
# Fleet integration: SLO control loop
# ---------------------------------------------------------------------------
class TestFleetSLOControlLoop:
    def test_ttft_regression_boosts_router_load_penalty(self):
        """Injected TTFT regression drives ok -> pending -> firing ->
        resolved through ``check_slo(now=)`` deterministically, and the
        FIRING alert measurably changes the affinity router's load
        penalty (restored on resolve)."""
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="affinity",
                             engine_kwargs=ENGINE_KW)
        seen = []
        fleet.enable_slo(rules=[SLORule(
            "ttft_p99", "engine_ttft_seconds", "p99", threshold=0.5,
            window_s=30.0, for_s=5.0, clear_for_s=10.0)],
            on_alert=seen.append, load_penalty_boost=4.0)
        base = fleet.load_penalty
        h = fleet.workers[0].registry.get("engine_ttft_seconds")
        assert h is not None                    # engine registers it
        for _ in range(50):
            h.observe(2.0)                      # injected regression
        assert fleet.check_slo(now=0.0) == []
        assert fleet.slo.states() == {"ttft_p99": "pending"}
        assert fleet.load_penalty == base       # pending does nothing
        ev = fleet.check_slo(now=5.0)
        assert [e["state"] for e in ev] == ["firing"]
        assert fleet.load_penalty == base * 4.0  # control loop closed
        assert fleet.slo.alert("ttft_p99").burn_rate > 1.0
        # regression over: no new observations, window slides past
        assert fleet.check_slo(now=50.0) == []  # hysteresis hold
        assert fleet.load_penalty == base * 4.0
        ev = fleet.check_slo(now=61.0)
        assert [e["state"] for e in ev] == ["resolved"]
        assert fleet.load_penalty == base       # restored
        assert [e["state"] for e in seen] == ["firing", "resolved"]
        # the router registry carries the alert counters for scraping
        snap = fleet.metrics.snapshot()
        assert snap["counters"]["slo_alerts_fired_total"] == 1
        assert snap["counters"]["slo_alerts_resolved_total"] == 1
        fleet.close()


# ---------------------------------------------------------------------------
# Fleet integration: shipper resilience + bit-identical serving
# ---------------------------------------------------------------------------
class TestFleetShipper:
    def test_raising_sink_never_perturbs_serving(self):
        """An always-raising sink: the shipper drops with backoff, its
        self-observation counters land in the fleet scrape body, and
        generation output is bit-identical to a shipper-disabled run."""
        m = shared_model()
        rng = np.random.RandomState(11)
        prompts = [rng.randint(1, 128, (8,)).astype(np.int32)
                   for _ in range(3)]
        expect = [solo_generate(m, p, 8) for p in prompts]

        def run(sinks):
            fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                                 engine_kwargs=ENGINE_KW)
            if sinks is not None:
                fleet.enable_shipper(sinks, interval_s=0.0,
                                     queue_max=2)
            reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
            fleet.run_until_drained()
            outs = [np.asarray(r.wait(timeout=60)).reshape(-1)
                    for r in reqs]
            return fleet, outs

        f_off, off = run(None)
        f_off.close()
        boom = _BoomSink()
        f_on, on = run([boom])
        for a, b, e in zip(off, on, expect):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, e.reshape(-1))
        assert boom.calls > 0                   # sink genuinely raised
        for _ in range(3):                      # keep collecting against
            f_on.shipper.flush()                # the full, backing-off
        st = f_on.shipper.stats()               # queue
        assert st["sink_errors"] > 0 and st["shipped"] == 0
        assert st["dropped"] > 0                # drop-oldest, counted
        assert st["queue_depth"] == 2           # bounded at queue_max
        text = f_on.aggregator().prometheus_text()
        assert 'shipper_sink_errors_total{worker="shipper"}' in text
        assert 'shipper_dropped_total{worker="shipper"}' in text
        f_on.close()

    def test_collect_telemetry_payload_shape(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        fleet.enable_slo()
        sink = _FlakySink(fail_first=0)
        fleet.enable_shipper([sink], interval_s=0.0)
        r = fleet.submit(np.arange(1, 9, dtype=np.int32),
                         max_new_tokens=4)
        fleet.run_until_drained()
        r.wait(timeout=60)
        fleet.shipper.flush()                   # ship the retirement
        assert sink.out
        last = sink.out[-1]
        assert last["kind"] == "fleet_telemetry"
        assert "engine_retired_total" in last["snapshot"]["counters"]
        assert last["slo"] == {"ttft_p99": "ok", "error_rate": "ok",
                               "queue_wait_p50": "ok"}
        shipped_traces = [t for p in sink.out for t in p["traces"]]
        assert [t["trace_id"] for t in shipped_traces] == \
            [r.trace.trace_id]                  # shipped exactly once
        assert shipped_traces[0]["state"] == "retired"
        fleet.close()


# ---------------------------------------------------------------------------
# Satellites: Prometheus escaping + merge_snapshots degenerate inputs
# ---------------------------------------------------------------------------
class TestPrometheusEscaping:
    PATHOLOGICAL = 'tail p99 \\ of "request\nlatency"'

    def test_pathological_help_is_escaped(self):
        reg = MetricsRegistry()
        reg.counter("weird_total", self.PATHOLOGICAL)
        text = reg.prometheus_text()
        want = 'tail p99 \\\\ of "request\\nlatency"'
        assert f"# HELP weird_total {want}" in text.splitlines()
        # no sample/HELP line was torn by the raw newline
        assert not any(ln.startswith("latency")
                       for ln in text.splitlines())

    def test_label_values_are_escaped(self):
        reg = MetricsRegistry()
        reg.counter("jobs_total", "jobs").inc()
        text = reg.prometheus_text(labels={"worker": 'w"0\\\n'})
        assert 'jobs_total{worker="w\\"0\\\\\\n"} 1' in \
            text.splitlines()

    def test_aggregator_escapes_help_and_labels(self):
        reg = MetricsRegistry()
        reg.counter("weird_total", self.PATHOLOGICAL).inc()
        agg = MetricsAggregator()
        agg.add('w"0\n', reg)
        text = agg.prometheus_text()
        want = 'tail p99 \\\\ of "request\\nlatency"'
        assert f"# HELP weird_total {want}" in text.splitlines()
        assert 'weird_total{worker="w\\"0\\n"} 1' in text.splitlines()


class TestMergeSnapshotsDegenerate:
    def test_union_rule_for_missing_metrics(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("only_a_total").inc(2)
        a.histogram("lat").observe(0.01)
        b.counter("only_b_total").inc(3)
        b.counter("only_a_total").inc(5)
        merged = merge_snapshots([a.snapshot(), b.snapshot()])
        assert merged["counters"] == {"only_a_total": 7.0,
                                      "only_b_total": 3.0}
        # a histogram present on one worker merges as-is
        assert merged["histograms"]["lat"]["count"] == 1
        assert merged["histograms"]["lat"]["p50"] == \
            a.snapshot()["histograms"]["lat"]["p50"]

    def test_single_snapshot_quantiles_round_trip(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        for v in (0.001, 0.01, 0.1, 1.0, 1.0, 1.0):
            h.observe(v)
        snap = reg.snapshot()
        merged = merge_snapshots([snap])
        for key in ("count", "sum", "min", "max", "p50", "p99"):
            assert merged["histograms"]["lat"][key] == \
                snap["histograms"]["lat"][key]

    def test_merge_of_empty_iterable_is_empty(self):
        assert merge_snapshots([]) == {"counters": {}, "gauges": {},
                                       "histograms": {}}

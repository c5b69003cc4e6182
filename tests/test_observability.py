"""Serving telemetry (ISSUE 3): metrics primitives (thread safety,
bucket edges, Prometheus exposition), request lifecycle traces with
injected clocks, the engine's end-to-end trace/registry wiring over the
debug llama, unified chrome-trace engine spans, the allocator
conservation invariant under preemption stress, and the engine stall
watchdog driven deterministically."""

import json
import logging
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (Counter, Gauge, Histogram,
                                      MetricsRegistry, RequestTrace,
                                      DEFAULT_LATENCY_BUCKETS)

from harness import drive, shared_model


class TestCounter:
    def test_inc_and_value(self):
        c = Counter("c_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_negative_inc_raises(self):
        c = Counter("c_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_concurrent_incs_lose_nothing(self):
        c = Counter("c_total")

        def worker():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000


class TestGauge:
    def test_set_inc_dec(self):
        g = Gauge("g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6.0

    def test_fn_reads_at_collection_time(self):
        """The one-source-of-truth contract: the gauge re-reads the
        callback on every .value, never caching a stale mirror."""
        box = {"v": 1}
        g = Gauge("g", fn=lambda: box["v"])
        assert g.value == 1.0
        box["v"] = 7
        assert g.value == 7.0

    def test_fn_exception_reads_nan(self):
        g = Gauge("g", fn=lambda: 1 / 0)
        assert g.value != g.value          # NaN, not a raised scrape


class TestHistogram:
    def test_default_buckets_cover_latency_range(self):
        assert DEFAULT_LATENCY_BUCKETS[0] == pytest.approx(1e-4)
        assert DEFAULT_LATENCY_BUCKETS[-1] > 100.0
        assert list(DEFAULT_LATENCY_BUCKETS) == \
            sorted(DEFAULT_LATENCY_BUCKETS)

    def test_le_edge_is_inclusive(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        h.observe(1.0)                     # == edge: counts in le=1.0
        cum = dict(h.cumulative())
        assert cum[1.0] == 1

    def test_overflow_lands_in_inf_only(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        h.observe(99.0)
        cum = h.cumulative()
        assert cum[-1] == (float("inf"), 1)
        assert all(c == 0 for _, c in cum[:-1])

    def test_cumulative_monotone_and_inf_equals_count(self):
        h = Histogram("h")
        rng = np.random.default_rng(0)
        for v in rng.uniform(1e-5, 200.0, 500):
            h.observe(float(v))
        cum = h.cumulative()
        counts = [c for _, c in cum]
        assert counts == sorted(counts)
        assert cum[-1][1] == h.count == 500

    def test_sum_min_max_quantiles(self):
        h = Histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
        for v in (0.5, 1.5, 3.0, 7.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 4 and s["sum"] == pytest.approx(12.0)
        assert s["min"] == 0.5 and s["max"] == 7.0
        assert h.quantile(0.5) == 2.0      # upper edge of holding bucket
        assert h.quantile(1.0) == 8.0

    def test_quantile_inf_bucket_caps_at_observed_max(self):
        h = Histogram("h", buckets=(1.0,))
        h.observe(42.0)
        assert h.quantile(0.99) == 42.0

    def test_timer_observes_elapsed(self):
        h = Histogram("h")
        with h.time():
            pass
        assert h.count == 1 and h.sum >= 0.0

    def test_non_increasing_edges_raise(self):
        with pytest.raises(ValueError):
            Histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            Histogram("h", buckets=(1.0, 1.0))


class TestMetricsRegistry:
    def test_get_or_create_returns_same_object(self):
        r = MetricsRegistry()
        assert r.counter("a_total") is r.counter("a_total")
        assert "a_total" in r and r.get("a_total") is not None

    def test_type_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError, match="Counter"):
            r.gauge("x")

    def test_snapshot_is_json_able(self):
        r = MetricsRegistry()
        r.counter("c_total").inc(3)
        r.gauge("g").set(2.5)
        h = r.histogram("lat_seconds")
        h.observe(0.01)
        snap = json.loads(json.dumps(r.snapshot()))
        assert snap["counters"]["c_total"] == 3
        assert snap["gauges"]["g"] == 2.5
        hs = snap["histograms"]["lat_seconds"]
        assert hs["count"] == 1 and hs["buckets"]["+Inf"] == 1

    def test_prometheus_text_format(self):
        r = MetricsRegistry()
        r.counter("req_total", "requests").inc(2)
        r.gauge("depth", "queue depth").set(4)
        h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = r.prometheus_text()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert "req_total 2" in text
        assert "# TYPE depth gauge" in text and "depth 4" in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_sum 5.05" in text
        assert "lat_seconds_count 2" in text


class TestRequestTrace:
    def test_derived_metrics_from_injected_clock(self):
        tr = RequestTrace(t=0.0)
        tr.mark("queued", t=1.0)
        tr.mark("admitted", t=3.0)
        tr.mark("first_token", t=4.0)
        tr.mark("retired", t=10.0)
        assert tr.ttft == 4.0
        assert tr.queue_wait == 2.0        # queued->admitted only
        assert tr.tpot(4) == pytest.approx(2.0)  # (10-4)/3
        assert tr.terminal == "retired"
        assert tr.is_monotone() and tr.is_complete()

    def test_queue_wait_sums_preemption_stints(self):
        tr = RequestTrace(t=0.0)
        tr.mark("queued", t=0.0)
        tr.mark("admitted", t=1.0)
        tr.mark("first_token", t=1.5)
        tr.mark("preempted", t=2.0)
        tr.mark("queued", t=2.0)
        tr.mark("admitted", t=5.0)
        tr.mark("retired", t=6.0)
        assert tr.queue_wait == pytest.approx(4.0)   # 1.0 + 3.0
        assert tr.preemptions == 1
        assert tr.is_complete()

    def test_no_queued_mark_charges_arrival_to_admitted(self):
        tr = RequestTrace(t=2.0)           # contiguous-mode direct admit
        tr.mark("admitted", t=5.0)
        assert tr.queue_wait == pytest.approx(3.0)

    def test_mark_once_skips_duplicates(self):
        tr = RequestTrace(t=0.0)
        assert tr.mark_once("first_token", t=1.0) == 1.0
        assert tr.mark_once("first_token", t=2.0) is None
        assert tr.times("first_token") == [1.0]

    def test_incomplete_without_first_token(self):
        tr = RequestTrace(t=0.0)
        tr.mark("admitted", t=1.0)
        tr.mark("retired", t=2.0)
        assert not tr.is_complete()

    def test_failed_is_terminal_and_complete(self):
        tr = RequestTrace(t=0.0)
        tr.mark("failed", t=1.0)
        assert tr.terminal == "failed" and tr.is_complete()

    def test_summary_json_able_and_ids_unique(self):
        a, b = RequestTrace(), RequestTrace()
        assert a.request_id != b.request_id
        json.dumps(a.summary())


class TestEngineLifecycleTelemetry:
    def test_every_retired_request_has_complete_trace(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(7)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8)
        reqs = [_Request(rng.randint(1, 128,
                                     (int(rng.randint(3, 12)),))
                         .astype(np.int32), int(rng.choice([3, 6])))
                for _ in range(5)]
        drive(eng, list(reqs))
        for r in reqs:
            r.wait(timeout=5)
            tr = r.trace
            assert tr.terminal == "retired"
            assert tr.is_monotone() and tr.is_complete()
            states = {s for s, _ in tr.events}
            assert {"arrival", "queued", "admitted", "first_token",
                    "decode_chunk", "retired"} <= states
            assert tr.ttft is not None and tr.ttft >= 0.0

    def test_registry_histograms_match_lifecycle_counts(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(9)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8)
        reqs = [_Request(rng.randint(1, 128, (6,)).astype(np.int32), 6)
                for _ in range(4)]
        drive(eng, list(reqs))
        snap = eng.metrics.snapshot()
        assert snap["counters"]["engine_admitted_total"] == 4
        assert snap["counters"]["engine_retired_total"] == 4
        assert snap["counters"]["engine_failed_total"] == 0
        # one TTFT / queue-wait observation per admission, one TPOT per
        # multi-token retire — the histograms ARE the lifecycle record
        assert snap["histograms"]["engine_ttft_seconds"]["count"] == 4
        assert snap["histograms"]["engine_queue_wait_seconds"][
            "count"] == 4
        assert snap["histograms"]["engine_tpot_seconds"]["count"] == 4
        assert snap["histograms"]["engine_chunk_seconds"]["count"] >= 1
        g = snap["gauges"]
        for name in ("engine_backlog", "engine_pool_free",
                     "allocator_in_use", "engine_pool_high_watermark",
                     "engine_batch_occupancy", "engine_prefix_hit_rate"):
            assert name in g, name
        assert g["engine_backlog"] == 0
        # stats() is a THIN view over the same registry
        st = eng.stats()
        # rows are gone but their published prefix pages stay cached —
        # the gauge reads the allocator, not a drifting mirror
        assert g["allocator_in_use"] == st["pool"]["used"]
        assert st["admitted"] == 4 and st["retired"] == 4
        assert st["pool"]["high_watermark"] == \
            g["engine_pool_high_watermark"]
        json.dumps(snap)
        assert "engine_ttft_seconds_bucket" in \
            eng.metrics.prometheus_text()

    def test_private_registries_do_not_cross_pollute(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(3)
        p = rng.randint(1, 128, (6,)).astype(np.int32)
        e1 = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                          block_size=8)
        e2 = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                          block_size=8)
        drive(e1, [_Request(p, 4)])
        assert e1.stats()["retired"] == 1
        assert e2.stats()["retired"] == 0

    def test_ttft_observed_once_across_preemption(self):
        """A preempted-and-resumed request keeps ONE first_token mark:
        the TTFT histogram must not double-count the resume."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(18)
        eng = DecodeEngine(m, capacity=3, s_max=64, chunk=4,
                           block_size=8, n_blocks=6)
        reqs = [_Request(rng.randint(1, 128,
                                     (int(rng.randint(3, 14)),))
                         .astype(np.int32),
                         int(rng.choice([3, 6, 10])),
                         priority=int(rng.randint(0, 3)))
                for _ in range(8)]
        queue, pending = list(reqs), []
        for _ in range(2000):
            while queue and len(pending) < 2:
                pending.append(queue.pop(0))
            eng.admit(pending)
            eng.decode_once()
            if not queue and not pending and eng.idle():
                break
        else:
            raise AssertionError("stress workload did not drain")
        preempted = sum(r.trace.preemptions for r in reqs)
        assert preempted >= 1              # the tiny pool forced some
        for r in reqs:
            assert r.trace.count("first_token") <= 1
            if r.trace.terminal == "retired":
                assert r.trace.is_complete()
        snap = eng.metrics.snapshot()
        assert snap["counters"]["engine_preempted_total"] == preempted
        assert snap["histograms"]["engine_ttft_seconds"]["count"] == \
            snap["counters"]["engine_admitted_total"] - preempted


class TestAllocatorConservation:
    def test_invariant_across_preemption_stress(self):
        """total_allocated - total_freed == in_use at EVERY engine step
        of a pool-starved preempting workload, and the pool drains to
        zero — the counter-drift class the satellite closes."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(18)
        eng = DecodeEngine(m, capacity=3, s_max=64, chunk=4,
                           block_size=8, n_blocks=6)
        reqs = [_Request(rng.randint(1, 128,
                                     (int(rng.randint(3, 14)),))
                         .astype(np.int32),
                         int(rng.choice([3, 6, 10])),
                         priority=int(rng.randint(0, 3)))
                for _ in range(8)]
        queue, pending = list(reqs), []
        a = eng._alloc
        for _ in range(2000):
            while queue and len(pending) < 2:
                pending.append(queue.pop(0))
            eng.admit(pending)
            eng.decode_once()
            assert a.total_allocated - a.total_freed == a.in_use
            if not queue and not pending and eng.idle():
                break
        else:
            raise AssertionError("stress workload did not drain")
        # cached prefix pages may legitimately stay resident; evicting
        # everything must take the pool back to exactly zero in use
        if eng._cache is not None:
            eng._cache.evict(eng.n_blocks)
        assert a.in_use == 0
        assert a.total_allocated == a.total_freed
        # the gauge reads the same source of truth
        assert eng.metrics.get("allocator_in_use").value == 0

    def test_gauge_tracks_live_allocator(self):
        from paddle_tpu.inference.paged_cache import BlockAllocator
        r = MetricsRegistry()
        a = BlockAllocator(8)
        r.gauge("allocator_in_use", fn=lambda: a.in_use)
        pages = a.allocate(3)
        assert r.get("allocator_in_use").value == 3
        a.free(pages)
        assert r.get("allocator_in_use").value == 0
        assert a.total_allocated - a.total_freed == a.in_use == 0


class TestChromeTraceUnifiedTimeline:
    def test_engine_spans_and_op_events_share_one_export(self, tmp_path):
        """The unified timeline: engine lifecycle spans (cat=engine)
        and op-dispatch instants land in ONE chrome trace."""
        from paddle_tpu import profiler
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(5)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8)
        prof = profiler.Profiler()
        prof.start()
        reqs = [_Request(rng.randint(1, 128, (6,)).astype(np.int32), 4)
                for _ in range(2)]
        pending = list(reqs)
        for _ in range(200):
            eng.admit(pending)
            eng.decode_once()
            # a host-side paddle op inside the window: the op instant
            # must interleave with the engine spans in the same export
            (paddle.to_tensor(np.ones((2, 2), np.float32)) * 2.0)
            if eng.idle() and not pending:
                break
        prof.stop()
        path = str(tmp_path / "trace.json")
        prof.export_chrome_tracing(path)
        data = json.load(open(path))
        by_cat = {}
        for e in data["traceEvents"]:
            by_cat.setdefault(e.get("cat"), set()).add(e["name"])
        assert "engine.prefill" in by_cat.get("engine", set())
        assert "engine.decode_chunk" in by_cat.get("engine", set())
        assert any(c != "engine" and c is not None for c in by_cat)

    def test_record_event_is_cheap_when_disabled(self):
        """Engine spans ride RecordEvent unconditionally — with no
        profiler enabled they must not emit anything."""
        from paddle_tpu import profiler
        from paddle_tpu.profiler import RecordEvent
        with RecordEvent("engine.decode_chunk", "engine"):
            pass
        prof = profiler.Profiler()
        prof.start()
        prof.stop()
        assert "engine.decode_chunk" not in prof.summary()["events"]


class TestEngineStallWatchdog:
    def _registry(self, steps=0, occupancy=1, backlog=0):
        r = MetricsRegistry()
        r.counter("engine_device_steps_total").inc(steps)
        r.gauge("engine_batch_occupancy").set(occupancy)
        r.gauge("engine_backlog").set(backlog)
        return r

    def _wd(self, registry, **kw):
        from paddle_tpu.distributed.watchdog import EngineStallWatchdog
        kw.setdefault("stall_s", 10.0)
        return EngineStallWatchdog(registry, **kw)

    def test_fires_once_per_stall_episode(self):
        r = self._registry(steps=5)
        events = []
        wd = self._wd(r, on_stall=events.append)
        assert wd.check(now=0.0) is None       # baseline
        assert wd.check(now=5.0) is None       # under threshold
        info = wd.check(now=15.0)              # static 15s while busy
        assert info is not None
        assert info["counter"] == "engine_device_steps_total"
        assert info["stalled_s"] == pytest.approx(15.0)
        assert info["snapshot"]["gauges"]["engine_batch_occupancy"] == 1
        assert wd.check(now=30.0) is None      # same episode: no re-fire
        assert events == [info] and wd.stalls == [info]

    def test_advancing_heartbeat_rearms(self):
        r = self._registry(steps=0)
        wd = self._wd(r)
        assert wd.check(now=0.0) is None
        assert wd.check(now=15.0) is not None  # first stall
        r.counter("engine_device_steps_total").inc(4)
        assert wd.check(now=20.0) is None      # moved: re-armed
        assert wd.check(now=35.0) is not None  # second distinct episode
        assert len(wd.stalls) == 2

    def test_idle_engine_never_stalls(self):
        r = self._registry(steps=3, occupancy=0, backlog=0)
        wd = self._wd(r)
        assert wd.check(now=0.0) is None
        assert wd.check(now=100.0) is None     # quiet != stalled
        # backlog alone (requests waiting, no rows) still counts as busy
        r.gauge("engine_backlog").set(2)
        assert wd.check(now=101.0) is None     # busy clock starts here
        assert wd.check(now=120.0) is not None

    def test_stall_dump_hits_event_log(self):
        from paddle_tpu.utils.log import default_event_log
        r = self._registry(steps=1)
        wd = self._wd(r)
        wd.check(now=0.0)
        mark = len(default_event_log.events("engine_stall"))
        assert wd.check(now=60.0) is not None
        evts = default_event_log.events("engine_stall")[mark:]
        assert len(evts) == 1
        assert evts[0]["snapshot"]["counters"][
            "engine_device_steps_total"] == 1

    def test_missing_counter_is_not_a_stall(self):
        wd = self._wd(MetricsRegistry())
        assert wd.check(now=0.0) is None
        assert wd.check(now=100.0) is None


class TestStructuredLogging:
    def test_kv_line_format(self):
        from paddle_tpu.utils.log import kv_line
        assert kv_line("admitted", req=3, slot=0) == \
            "admitted req=3 slot=0"
        assert kv_line("tick") == "tick"

    def test_log_kv_respects_logger_level(self, caplog):
        from paddle_tpu.utils.log import log_kv
        logger = logging.getLogger("pt.test.obs")
        logger.setLevel(logging.INFO)
        logger.propagate = True
        with caplog.at_level(logging.INFO, logger="pt.test.obs"):
            log_kv(logger, "retired", req=1, ttft_s=0.5)
            log_kv(logger, "chatter", level=logging.DEBUG, x=1)
        assert "retired req=1 ttft_s=0.5" in caplog.text
        assert "chatter" not in caplog.text

    def test_pt_log_level_env_knob(self, monkeypatch):
        from paddle_tpu.utils import log as ptlog
        monkeypatch.setenv("PT_LOG_LEVEL", "debug")
        assert ptlog._glog_level() == logging.DEBUG
        monkeypatch.setenv("PT_LOG_LEVEL", "40")
        assert ptlog._glog_level() == logging.ERROR
        monkeypatch.delenv("PT_LOG_LEVEL")
        monkeypatch.setenv("GLOG_v", "0")
        assert ptlog._glog_level() == logging.WARNING

    def test_server_stats_is_registry_view(self):
        """BatchingServer counts submissions through the registry and
        exposes a thin stats() view (engine stats ride along in
        continuous mode)."""
        from paddle_tpu.inference.serving import (BatchingServer,
                                                  GenerationPredictor)
        m = shared_model()
        srv = BatchingServer(GenerationPredictor(m), max_batch=2,
                             max_new_tokens=4, continuous=True,
                             engine_kwargs={"s_max": 64, "chunk": 4,
                                            "block_size": 8})
        try:
            assert srv.metrics is srv.engine.metrics
            r = srv.submit(np.array([1, 5, 9], np.int32))
            r.wait(timeout=120)
            st = srv.stats()
            assert st["submitted"] == 1
            assert st["engine"]["retired"] == 1
            snap = srv.metrics.snapshot()
            assert snap["counters"]["server_submitted_total"] == 1
            assert snap["counters"]["engine_retired_total"] == 1
        finally:
            srv.close()


class TestMergeSnapshots:
    """ISSUE 4: snapshot merging must behave like observing the UNION
    of samples into one histogram — checked property-style (random
    sample sets, associativity, commutativity) over the fixed
    log-spaced edges that make the merge well-defined."""

    @staticmethod
    def _registry_with(samples, counter=0.0, gauge=0.0):
        from paddle_tpu.observability import MetricsRegistry
        r = MetricsRegistry()
        r.counter("reqs_total").inc(counter)
        r.gauge("occupancy").set(gauge)
        h = r.histogram("lat_seconds")
        for v in samples:
            h.observe(v)
        return r

    @staticmethod
    def _sample_sets(seed, k=3):
        rng = np.random.RandomState(seed)
        out = []
        for _ in range(k):
            n = int(rng.randint(0, 40))
            # span the full bucket range incl. sub-min and overflow
            out.append(list(10 ** rng.uniform(-4.5, 2.5, size=n)))
        return out

    def _assert_hist_equal(self, a, b):
        assert a["count"] == b["count"]
        assert a["buckets"] == b["buckets"]
        assert a["sum"] == pytest.approx(b["sum"])
        for k in ("min", "max"):
            if a[k] is None:
                assert b[k] is None
            else:
                assert a[k] == pytest.approx(b[k])
        # snapshot bucket keys are 'g'-formatted (6 sig figs), so a
        # merged quantile can differ from the live histogram's exact
        # edge only by that serialization rounding
        assert a["p50"] == pytest.approx(b["p50"], rel=1e-5)
        assert a["p99"] == pytest.approx(b["p99"], rel=1e-5)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_merge_equals_union_observation(self, seed):
        from paddle_tpu.observability import merge_snapshots
        sets = self._sample_sets(seed)
        snaps = [self._registry_with(s, counter=i + 1, gauge=i).snapshot()
                 for i, s in enumerate(sets)]
        merged = merge_snapshots(snaps)
        union = self._registry_with(
            [v for s in sets for v in s],
            counter=sum(range(1, len(sets) + 1)),
            gauge=sum(range(len(sets)))).snapshot()
        assert merged["counters"] == pytest.approx(union["counters"])
        assert merged["gauges"] == pytest.approx(union["gauges"])
        self._assert_hist_equal(merged["histograms"]["lat_seconds"],
                                union["histograms"]["lat_seconds"])

    @pytest.mark.parametrize("seed", [7, 8])
    def test_merge_is_commutative(self, seed):
        from paddle_tpu.observability import merge_snapshots
        snaps = [self._registry_with(s, counter=i).snapshot()
                 for i, s in enumerate(self._sample_sets(seed))]
        fwd = merge_snapshots(snaps)
        rev = merge_snapshots(list(reversed(snaps)))
        assert fwd["counters"] == pytest.approx(rev["counters"])
        self._assert_hist_equal(fwd["histograms"]["lat_seconds"],
                                rev["histograms"]["lat_seconds"])

    @pytest.mark.parametrize("seed", [11, 12])
    def test_merge_is_associative(self, seed):
        from paddle_tpu.observability import merge_snapshots
        a, b, c = [self._registry_with(s).snapshot()
                   for s in self._sample_sets(seed, k=3)]
        left = merge_snapshots([merge_snapshots([a, b]), c])
        right = merge_snapshots([a, merge_snapshots([b, c])])
        assert left["counters"] == pytest.approx(right["counters"])
        self._assert_hist_equal(left["histograms"]["lat_seconds"],
                                right["histograms"]["lat_seconds"])

    def test_empty_and_single_inputs(self):
        from paddle_tpu.observability import merge_snapshots
        assert merge_snapshots([]) == {"counters": {}, "gauges": {},
                                       "histograms": {}}
        snap = self._registry_with([0.01], counter=2).snapshot()
        one = merge_snapshots([snap])
        assert one["counters"] == snap["counters"]
        self._assert_hist_equal(one["histograms"]["lat_seconds"],
                                snap["histograms"]["lat_seconds"])

    def test_nan_gauges_are_skipped(self):
        from paddle_tpu.observability import MetricsRegistry, merge_snapshots
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.gauge("g", fn=lambda: (_ for _ in ()).throw(RuntimeError()))
        r2.gauge("g").set(3.0)
        m = merge_snapshots([r1.snapshot(), r2.snapshot()])
        assert m["gauges"]["g"] == 3.0

    def test_mismatched_bucket_edges_raise(self):
        from paddle_tpu.observability import MetricsRegistry, merge_snapshots
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.histogram("h", buckets=(1.0, 2.0))
        r2.histogram("h", buckets=(1.0, 4.0))
        with pytest.raises(ValueError, match="bucket edges"):
            merge_snapshots([r1.snapshot(), r2.snapshot()])


class TestPrometheusLabels:
    def test_no_labels_is_byte_identical(self):
        r = MetricsRegistry()
        r.counter("c_total", "help").inc(2)
        r.histogram("h_seconds").observe(0.01)
        base = r.prometheus_text()
        assert r.prometheus_text(labels=None) == base
        assert r.prometheus_text(labels={}) == base

    def test_labels_on_every_sample_sorted_le_last(self):
        r = MetricsRegistry()
        r.counter("c_total").inc(2)
        r.gauge("g").set(1.5)
        r.histogram("h_seconds").observe(0.01)
        text = r.prometheus_text(labels={"worker": "w3", "host": "a"})
        assert 'c_total{host="a",worker="w3"} 2' in text
        assert 'g{host="a",worker="w3"} 1.5' in text
        assert 'h_seconds_bucket{host="a",worker="w3",le="+Inf"} 1' \
            in text
        assert 'h_seconds_sum{host="a",worker="w3"}' in text
        assert 'h_seconds_count{host="a",worker="w3"} 1' in text
        # HELP/TYPE headers stay unlabeled
        assert "# TYPE c_total counter" in text


class TestQuantileFromBuckets:
    """ISSUE 13 satellite: the shared cumulative-bucket quantile rule,
    exercised on the edge buckets (the dedup target for
    merge_snapshots / SLO windows / StepProfiler.summary)."""

    def test_empty_returns_empty_default(self):
        from paddle_tpu.observability import quantile_from_buckets
        assert quantile_from_buckets(0.5, {}, 0) == 0.0
        assert quantile_from_buckets(
            0.99, {"+Inf": 0}, 0, empty=None) is None

    def test_median_lands_on_covering_edge(self):
        from paddle_tpu.observability import quantile_from_buckets
        # 3 of 4 samples at/below 2e-4 (second edge): p50 -> 0.0002
        buckets = {"0.0001": 1, "0.0002": 3, "+Inf": 4}
        assert quantile_from_buckets(0.5, buckets, 4) == \
            pytest.approx(2e-4)

    def test_p99_clamps_to_observed_max(self):
        from paddle_tpu.observability import quantile_from_buckets
        # all mass in +Inf: without a max the edge would be inf; the
        # observed max is the honest clamp
        buckets = {"0.0001": 0, "+Inf": 10}
        assert quantile_from_buckets(0.99, buckets, 10, 7.5) == 7.5

    def test_float_and_string_keys_agree(self):
        from paddle_tpu.observability import quantile_from_buckets
        total = 8
        s = {"0.0001": 2, "0.0004": 6, "+Inf": 8}
        f = {1e-4: 2, 4e-4: 6, float("inf"): 8}
        for q in (0.25, 0.5, 0.9, 0.99):
            assert quantile_from_buckets(q, s, total) == \
                pytest.approx(quantile_from_buckets(q, f, total))

    def test_matches_registry_snapshot_quantiles(self):
        from paddle_tpu.observability import quantile_from_buckets
        r = MetricsRegistry()
        h = r.histogram("h_seconds")
        rng = np.random.RandomState(3)
        for v in 10 ** rng.uniform(-4, 1, size=64):
            h.observe(float(v))
        snap = r.snapshot()["histograms"]["h_seconds"]
        for q, key in ((0.5, "p50"), (0.99, "p99")):
            assert quantile_from_buckets(
                q, snap["buckets"], snap["count"],
                snap["max"]) == pytest.approx(snap[key], rel=1e-5)


class TestFlightRecorder:
    def _rec(self, **kw):
        from paddle_tpu.observability import FlightRecorder
        t = [0.0]

        def clock():
            t[0] += 0.25
            return t[0]

        return FlightRecorder(clock=clock, **kw)

    def test_ring_bound_and_drop_accounting(self):
        rec = self._rec(capacity=4, name="w0")
        for i in range(10):
            rec.record("tick", i=i)
        evts = rec.events()
        assert len(rec) == 4 and len(evts) == 4
        assert [e["i"] for e in evts] == [6, 7, 8, 9]
        snap = rec.snapshot()
        assert snap["seq"] == 10 and snap["dropped"] == 6
        assert snap["capacity"] == 4 and snap["name"] == "w0"

    def test_seq_and_clock_stamps(self):
        rec = self._rec(capacity=8)
        rec.record("a")
        rec.record("b")
        a, b = rec.events()
        assert (a["seq"], b["seq"]) == (1, 2)
        assert a["t"] == 0.25 and b["t"] == 0.5

    def test_kind_filter_and_tail(self):
        rec = self._rec(capacity=16)
        for i in range(6):
            rec.record("even" if i % 2 == 0 else "odd", i=i)
        assert [e["i"] for e in rec.events(kind="odd")] == [1, 3, 5]
        assert [e["i"] for e in rec.events(n=2)] == [4, 5]

    def test_forwarding_stamps_src(self):
        fleet = self._rec(capacity=8, name="fleet")
        w = self._rec(capacity=8, name="w1", forward_to=fleet)
        w.record("fault", step=3, src="should_be_replaced")
        local, = w.events()
        assert local["src"] == "should_be_replaced"  # local keeps it
        fwd, = fleet.events()
        assert fwd["kind"] == "fault" and fwd["step"] == 3
        assert fwd["src"] == "w1"      # forwarded copy is attributed

    def test_fn_gauges_registered(self):
        from paddle_tpu.observability import FlightRecorder
        r = MetricsRegistry()
        rec = FlightRecorder(capacity=2, registry=r)
        for _ in range(5):
            rec.record("x")
        g = r.snapshot()["gauges"]
        assert g["flight_events_seen"] == 5
        assert g["flight_events_dropped"] == 3

    def test_clear_keeps_seen(self):
        rec = self._rec(capacity=4)
        rec.record("x")
        rec.clear()
        assert len(rec) == 0
        assert rec.snapshot()["seq"] == 1


class TestStepProfiler:
    def _prof(self, **kw):
        from paddle_tpu.observability import StepProfiler
        t = [0.0]

        def clock():
            t[0] += 0.001
            return t[0]

        return StepProfiler(clock=clock, **kw), t

    def test_phase_ring_and_summary(self):
        prof, _ = self._prof(capacity=8, worker_id="w0")
        for _ in range(3):
            prof.begin_step()
            with prof.phase("launch"):
                pass
            with prof.phase("host_sync"):
                pass
            prof.end_step()
        s = prof.summary()
        assert s["worker"] == "w0" and s["steps"] == 3
        assert set(s["phases"]) == {"launch", "host_sync"}
        ph = s["phases"]["launch"]
        # ticking clock: every span is exactly one 1ms tick wide
        assert ph["count"] == 3
        assert ph["max_s"] == pytest.approx(0.001)
        assert ph["p50_s"] >= 0.001
        assert s["step_wall"]["count"] == 3

    def test_rings_are_bounded(self):
        prof, _ = self._prof(capacity=4)
        for _ in range(10):
            prof.begin_step()
            with prof.phase("publish"):
                pass
            prof.end_step()
        s = prof.summary()
        assert s["steps"] == 10          # counter keeps counting
        assert s["window"] == 4          # ring keeps the newest 4
        assert s["phases"]["publish"]["count"] == 4

    def test_end_step_without_begin_is_none(self):
        prof, _ = self._prof()
        assert prof.end_step() is None

    def test_unknown_phase_raises(self):
        prof, _ = self._prof()
        with pytest.raises(KeyError):
            prof.phase("not_a_phase")

    def test_registry_histogram_and_gauges(self):
        from paddle_tpu.observability import StepProfiler
        r = MetricsRegistry()
        t = [0.0]

        def clock():
            t[0] += 0.002
            return t[0]

        prof = StepProfiler(clock=clock, registry=r)
        prof.begin_step()
        with prof.phase("admission"):
            pass
        prof.end_step()
        snap = r.snapshot()
        assert snap["histograms"]["engine_step_phase_seconds"][
            "count"] == 1
        assert snap["gauges"]["engine_profiled_steps"] == 1
        assert snap["gauges"]["engine_step_wall_ewma_seconds"] > 0

    def test_outlier_flags_counter_and_flight(self):
        from paddle_tpu.observability import (FlightRecorder,
                                              StepProfiler)
        r = MetricsRegistry()
        rec = FlightRecorder(capacity=16)
        t = [0.0]
        dur = [0.001]

        def clock():
            t[0] += dur[0]
            return t[0]

        prof = StepProfiler(clock=clock, registry=r, recorder=rec,
                            worker_id="w9", outlier_min_steps=4)
        for _ in range(20):
            prof.begin_step()
            prof.end_step()
        dur[0] = 1.0                     # one pathological step
        prof.begin_step()
        prof.end_step()
        assert r.get("engine_step_outliers_total").value == 1
        ev, = rec.events(kind="phase_outlier")
        assert ev["worker"] == "w9" and ev["wall_s"] >= 1.0

    def test_to_events_chrome_shape(self):
        prof, _ = self._prof(capacity=8, worker_id="w0")
        prof.begin_step()
        with prof.phase("launch"):
            pass
        prof.end_step()
        evts = prof.to_events(pid=7)
        steps = [e for e in evts if e["name"] == "engine.step"]
        phases = [e for e in evts if e["name"] == "launch"]
        assert len(steps) == 1 and len(phases) == 1
        for e in evts:
            assert e["ph"] == "X" and e["cat"] == "profile"
            assert e["pid"] == 7 and e["dur"] > 0
        assert steps[0]["tid"] == 0 and phases[0]["tid"] == 1


class TestHostPhasesInTheTrace:
    """ISSUE 40: the serving loop's phases tile the serving thread's
    time, every paged launch's annotation says which launch it is, and
    a device trace taken meanwhile holds both (on the CPU: the host
    events alone)."""

    PROMPTS = (5, 12, 9, 7, 11)
    NEW = (21, 29, 17, 25, 19)
    TOP = ("poll", "admission", "prepare", "launch", "host_sync",
           "account", "publish")

    @staticmethod
    def _prompts(seed=40):
        rng = np.random.RandomState(seed)
        return [rng.randint(1, 128, (n,)).astype(np.int32)
                for n in TestHostPhasesInTheTrace.PROMPTS]

    def _serve(self, profile):
        """The prompts through an engine of two slots, three at once and
        two behind them; (engine, served sequences)."""
        from paddle_tpu.inference.serving import DecodeEngine
        from harness import ENGINE_KW
        eng = DecodeEngine(shared_model(), **ENGINE_KW, profile=profile)
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(self._prompts(), self.NEW)]
        drive(eng)
        return eng, [np.asarray(r.wait(timeout=120)) for r in reqs]

    @staticmethod
    def _recording(monkeypatch):
        """Stand in for ``jax.profiler.TraceAnnotation``: every span the
        profiler opens, as (name, arguments)."""
        import contextlib
        from paddle_tpu.observability import profiling
        made = []

        def factory(name, **args):
            made.append((name, args))
            return contextlib.nullcontext()

        monkeypatch.setattr(profiling, "_annotation", factory)
        return made

    def test_top_level_phases_tile_the_serving_loop(self, monkeypatch):
        import time
        from paddle_tpu.inference.serving import (BatchingServer,
                                                  GenerationPredictor)
        from paddle_tpu.observability import profiling
        from harness import ENGINE_KW
        clock = time.perf_counter
        made = []

        class Stamped:
            """What a trace would hold of a span, on the test's clock."""

            def __init__(self, name):
                self.name = name

            def __enter__(self):
                self.t0 = clock()

            def __exit__(self, *exc):
                made.append((self.t0, clock(), self.name))

        monkeypatch.setattr(profiling, "_annotation",
                            lambda name, **args: Stamped(name))
        kw = {k: v for k, v in ENGINE_KW.items() if k != "capacity"}
        srv = BatchingServer(
            GenerationPredictor(shared_model()), max_batch=2,
            continuous=True, engine_kwargs={**kw, "profile": True})
        try:
            # warm: every program compiled before the loop is read
            for p in self._prompts(seed=41)[:2]:
                srv.submit(p, max_new_tokens=5).wait(timeout=120)
            # a decode chunk as long as a chip's, so that the host's
            # microseconds between two phases weigh what they weigh there
            decode = srv.engine._decode
            srv.engine._decode = lambda *a: (time.sleep(0.03), decode(*a))[1]
            t_warm = clock()
            for h in [srv.submit(p, max_new_tokens=n) for p, n
                      in zip(self._prompts(), self.NEW)]:
                h.wait(timeout=120)
        finally:
            srv.close()
        spans = sorted(s for s in made if s[0] >= t_warm)
        steps = [s for s in spans if s[2] == "engine.step"]
        spans = [(a, b, n[len("engine."):]) for a, b, n in spans
                 if n != "engine.step"]
        assert {n for _, _, n in spans} == set(self.TOP)
        top, end = [], float("-inf")
        for a, b, name in spans:
            if b <= end:
                continue                # nested: a prefill's launch
            assert a >= end, f"{name} opens inside {top[-1][2]}"
            top.append((a, b, name))
            end = b
        first = next(i for i, s in enumerate(top) if s[2] == "poll")
        last = max(i for i, s in enumerate(top) if s[2] == "publish")
        top = top[first:last + 1]
        names = [n for _, _, n in top]
        assert names.count("launch") >= 12 <= names.count("admission")
        # loop order: a step's phases behind its admission; the reads
        # (of the prefills launched ahead, then of the chunk) all lie
        # behind the decode launch
        several = 0
        for i in (i for i, n in enumerate(names) if n == "prepare"):
            assert names[i - 1:i + 3] == ["admission", "prepare",
                                          "launch", "host_sync"]
            j = i + 3
            while names[j] == "host_sync":
                j += 1
            several += j > i + 3
            assert names[j:j + 2] == ["account", "publish"]
            assert any(a <= top[i][0] and top[j + 1][1] <= b
                       for a, b, _ in steps)
        assert several                  # some chunk followed a prefill
        # a prefill's own launch lies inside admission, its read-back
        # does not
        inside = [(a, b, n) for a, b, n in spans
                  if any(x <= a and b <= y and (a, b, n) != (x, y, m)
                         for x, y, m in top if m == "admission")]
        assert {n for _, _, n in inside} == {"launch"}
        covered = sum(b - a for a, b, _ in top)
        whole = top[-1][1] - top[0][0]
        assert covered <= whole
        assert (whole - covered) / whole < 0.01

    def test_launch_annotations_carry_the_launch(self, monkeypatch):
        made = self._recording(monkeypatch)
        eng, _ = self._serve(profile=True)
        launches = [a for n, a in made if n == "engine.launch" and a]
        log = eng.stats()["launches"]
        assert len(launches) == len(log) >= 12
        assert {a["kind"] for a in launches} == {"decode", "prefill"}
        for i, (a, entry) in enumerate(zip(launches, log)):
            t, kind, units, rows, tokens = entry[:5]
            assert a["launch"] == i
            assert (a["kind"], a["units"], a["rows"], a["tokens"]) == \
                (kind, units, rows, tokens)
            assert 0 <= a["t_ns"] / 1e9 - t < 0.05
        names = {n for n, _ in made}
        assert names >= {"engine.step", "engine.admission",
                         "engine.prepare", "engine.host_sync",
                         "engine.account", "engine.publish"}
        # every step that was opened was closed
        assert eng.profile._step_ann is None

    def test_profile_off_opens_no_annotation(self, monkeypatch):
        from paddle_tpu.inference import serving
        made = self._recording(monkeypatch)
        eng, served = self._serve(profile=None)
        assert made == []
        assert eng.profile is None and eng._launches is None
        for name in self.TOP:
            assert serving._phase(eng.profile, name) is serving._NOPROF
        assert eng._launch_args("decode", 4, 2, 80) is None
        _, profiled = self._serve(profile=True)
        assert made
        for a, b in zip(served, profiled):
            np.testing.assert_array_equal(a, b)

    def test_a_span_takes_arguments_for_one_entry(self, monkeypatch):
        from paddle_tpu.observability import StepProfiler
        made = self._recording(monkeypatch)
        prof = StepProfiler()
        with prof.phase("launch", {"launch": 7, "kind": "decode"}):
            pass
        with prof.phase("launch"):
            pass
        (n1, a1), (n2, a2) = made
        assert n1 == n2 == "engine.launch"
        assert a1["launch"] == 7 and a1["kind"] == "decode"
        assert a1["t_ns"] > 0 and a2 == {}

    def test_a_trace_holds_the_spans_with_their_arguments(self, tmp_path):
        import jax
        from benchmark.lib import host_spans
        from paddle_tpu.inference.serving import DecodeEngine
        from harness import ENGINE_KW
        eng = DecodeEngine(shared_model(), **ENGINE_KW, profile=True)
        eng.submit(self._prompts(seed=41)[0], max_new_tokens=5)
        drive(eng)                      # compiled before the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            reqs = [eng.submit(p, max_new_tokens=9)
                    for p in self._prompts()[:2]]
            drive(eng)
        finally:
            jax.profiler.stop_trace()
        assert all(r.wait(timeout=120) is not None for r in reqs)
        path, = tmp_path.rglob("*.xplane.pb")
        spans = host_spans.serving_line(host_spans.load(str(path)))
        by = {}
        for e in spans:
            by.setdefault(e.name, []).append(e)
        assert {"step", "admission", "prepare", "launch", "host_sync",
                "account", "publish"} <= set(by)
        launches = [e for e in by["launch"] if "kind" in e.args]
        log = eng.stats()["launches"][-len(launches):]
        assert len(launches) >= 4
        for e, entry in zip(launches, log):
            assert [e.args[k] for k in ("kind", "units", "rows",
                                        "tokens")] == entry[1:5]
        idx = [e.args["launch"] for e in launches]
        assert idx == list(range(idx[0], idx[0] + len(idx)))
        # t_ns ties perf_counter to the trace's clock: one offset
        offs = [e.start_ns - e.args["t_ns"] for e in launches]
        assert max(offs) - min(offs) < 5e6
        # a decode launch lies in its step, its host_sync behind it
        d = next(e for e in launches if e.args["kind"] == "decode")
        assert any(s.start_ns <= d.start_ns and d.end_ns <= s.end_ns
                   for s in by["step"])
        assert any(d.end_ns <= h.start_ns < d.end_ns + 1e6
                   for h in by["host_sync"])
        pieces = host_spans.innermost(spans)
        assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))


class TestCompileTracker:
    def _tracker(self, **kw):
        from paddle_tpu.observability import CompileTracker
        t = [0.0]

        def clock():
            t[0] += 0.5
            return t[0]

        return CompileTracker(clock=clock, **kw)

    def test_first_seen_signature_counts_once(self):
        tr = self._tracker()
        fn = tr.wrap("decode", lambda x: x, key=4)
        a = np.zeros((2, 4), np.float32)
        fn(a)
        fn(a)
        fn(np.zeros((2, 8), np.float32))    # new shape -> new compile
        assert tr.stats() == {"compiles": 2, "unexpected": 0,
                              "warm": False}
        log = tr.compile_log()
        assert [e["program"] for e in log] == ["decode", "decode"]
        assert log[0]["bucket_key"] == 4
        assert log[0]["wall_s"] == pytest.approx(0.5)
        assert tr.programs() == {"decode": 2}

    def test_post_warmup_compile_is_unexpected(self):
        from paddle_tpu.observability import FlightRecorder
        r = MetricsRegistry()
        rec = FlightRecorder(capacity=8)
        tr = self._tracker(registry=r, recorder=rec, worker_id="w1")
        fn = tr.wrap("prefill", lambda x: x)
        fn(np.zeros((1, 4), np.int32))
        tr.warmup_done()
        fn(np.zeros((1, 4), np.int32))      # seen: no new compile
        assert tr.stats()["unexpected"] == 0
        fn(np.zeros((1, 16), np.int32))     # stray shape post-warmup
        st = tr.stats()
        assert st == {"compiles": 2, "unexpected": 1, "warm": True}
        snap = r.snapshot()
        assert snap["counters"]["engine_compiles_total"] == 2
        assert snap["gauges"]["engine_unexpected_compiles"] == 1
        kinds = [e["kind"] for e in rec.events()]
        assert kinds == ["compile", "unexpected_compile"]
        assert tr.compile_log()[-1]["post_warmup"] is True

    def test_signature_covers_leaves_and_scalars(self):
        from paddle_tpu.observability import CompileTracker
        sig = CompileTracker.signature(
            (np.zeros((2, 3), np.float32), 7))
        assert sig == ((((2, 3)), "float32"), "int")


class TestDebugHTTPSurface:
    """ISSUE 13 satellite: /healthz, debug routes, the self-diagnosing
    404 and explicit Content-Type on every response."""

    def _serve(self, debug=None):
        from paddle_tpu.inference.fleet_metrics import (
            MetricsAggregator, MetricsHTTPServer)
        r = MetricsRegistry()
        r.counter("c_total").inc()
        agg = MetricsAggregator({"w0": r})
        return MetricsHTTPServer(agg, debug=debug).start()

    @staticmethod
    def _get(srv, path):
        import urllib.error
        import urllib.request
        try:
            resp = urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}{path}", timeout=10)
            return resp.status, resp.headers.get("Content-Type"), \
                resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.headers.get("Content-Type"), e.read()

    def test_healthz(self):
        srv = self._serve()
        try:
            code, ctype, body = self._get(srv, "/healthz")
        finally:
            srv.close()
        assert code == 200 and ctype == "application/json"
        assert json.loads(body) == {"status": "ok"}

    def test_debug_route_serves_provider_json(self):
        srv = self._serve(debug={"statusz": lambda: {"x": 1}})
        try:
            code, ctype, body = self._get(srv, "/statusz")
        finally:
            srv.close()
        assert code == 200 and ctype == "application/json"
        assert json.loads(body) == {"x": 1}

    def test_404_lists_served_paths(self):
        srv = self._serve(debug={"flightz": lambda: []})
        try:
            code, ctype, body = self._get(srv, "/nope")
        finally:
            srv.close()
        assert code == 404
        assert ctype.startswith("text/plain")
        text = body.decode()
        for p in ("/metrics", "/metrics.json", "/healthz", "/flightz"):
            assert p in text

    def test_raising_provider_is_500_not_wedge(self):
        def boom():
            raise RuntimeError("kaput")

        srv = self._serve(debug={"statusz": boom})
        try:
            code, ctype, body = self._get(srv, "/statusz")
            # server still answers afterwards
            ok, _, _ = self._get(srv, "/healthz")
        finally:
            srv.close()
        assert code == 500 and ctype.startswith("text/plain")
        assert b"RuntimeError" in body and b"kaput" in body
        assert ok == 200

    def test_metrics_content_types(self):
        srv = self._serve()
        try:
            _, ct_text, _ = self._get(srv, "/metrics")
            _, ct_json, body = self._get(srv, "/metrics.json")
        finally:
            srv.close()
        assert ct_text.startswith("text/plain")
        assert ct_json == "application/json"
        assert "fleet" in json.loads(body)

"""Serving fleet (ISSUE 4): GlobalPrefixDirectory indexing and cache
wiring, prefix-affinity vs round-robin routing, failover (killed
worker, raising step, watchdog stall) with bit-identical completion on
survivors, worker_id threading, and the cross-worker metrics
aggregator + stdlib scrape endpoint."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.inference.fleet import GlobalPrefixDirectory, ServingFleet
from paddle_tpu.inference.fleet_metrics import (MetricsAggregator,
                                                MetricsHTTPServer)
from paddle_tpu.observability import MetricsRegistry

from harness import ENGINE_KW, shared_model, solo_generate


class TestGlobalPrefixDirectory:
    def test_full_blocks_only(self):
        d = GlobalPrefixDirectory(4)
        d.on_insert("w0", list(range(10)))      # 2 full blocks + tail 2
        assert d.cached_tokens("w0", list(range(10))) == 8
        assert d.cached_tokens("w0", list(range(4))) == 4
        assert d.cached_tokens("w0", [9, 9, 9, 9]) == 0
        assert d.cached_tokens("w1", list(range(10))) == 0

    def test_partial_insert_not_indexed(self):
        d = GlobalPrefixDirectory(4)
        d.on_insert("w0", [1, 2, 3])            # sub-block: no signal
        assert d.cached_tokens("w0", [1, 2, 3, 4]) == 0
        assert d.stats() == {"w0": 0}

    def test_evict_removes_deepest_only(self):
        d = GlobalPrefixDirectory(4)
        d.on_insert("w0", list(range(12)))      # chain depth 3
        d.on_evict("w0", list(range(12)))       # victim = deepest node
        assert d.cached_tokens("w0", list(range(12))) == 8
        d.on_evict("w0", list(range(8)))
        assert d.cached_tokens("w0", list(range(12))) == 4

    def test_partial_leaf_evict_is_noop(self):
        d = GlobalPrefixDirectory(4)
        d.on_insert("w0", list(range(8)))
        d.on_evict("w0", list(range(7)))        # partial path: ignored
        assert d.cached_tokens("w0", list(range(8))) == 8

    def test_drop_worker_wipes(self):
        d = GlobalPrefixDirectory(4)
        d.on_insert("w0", list(range(8)))
        d.on_insert("w1", list(range(8)))
        d.drop_worker("w0")
        assert d.cached_tokens("w0", list(range(8))) == 0
        assert d.cached_tokens("w1", list(range(8))) == 8

    def test_wired_through_prefix_cache(self):
        """The listener hook on PrefixCache keeps the directory in sync
        with real insert/evict traffic, including the cascading evict's
        per-node notifications."""
        from paddle_tpu.inference.paged_cache import BlockAllocator
        from paddle_tpu.inference.prefix_cache import PrefixCache
        d = GlobalPrefixDirectory(4)
        a = BlockAllocator(9)
        c = PrefixCache(a, 4, listener=d.listener("w0"))
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        pages = a.allocate(2)
        c.insert(toks, pages)
        for p in pages:                 # row released; cache's ref holds
            a.decref(p)
        assert d.cached_tokens("w0", toks) == 8
        assert c.evict(2) == 2          # cascades leaf then parent
        assert d.cached_tokens("w0", toks) == 0
        assert d.stats() == {"w0": 0}

    def test_listener_fault_does_not_break_publish(self):
        from paddle_tpu.inference.paged_cache import BlockAllocator
        from paddle_tpu.inference.prefix_cache import PrefixCache

        class Boom:
            def on_insert(self, tokens):
                raise RuntimeError("listener bug")

            def on_evict(self, tokens):
                raise RuntimeError("listener bug")

        a = BlockAllocator(9)
        c = PrefixCache(a, 4, listener=Boom())
        pages = a.allocate(2)
        assert c.insert([1, 2, 3, 4, 5], pages) == 2    # no raise
        for p in pages:
            a.decref(p)
        assert c.evict(2) == 2                          # no raise


class TestRouting:
    def test_affinity_follows_published_prefix(self):
        """Serial shared-prefix traffic: once the first request retires
        and publishes its pages, every follow-up with the same system
        prompt routes to THAT worker (directory hit beats the load
        tie), and the affinity counter records it."""
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="affinity",
                             engine_kwargs=ENGINE_KW)
        rng = np.random.RandomState(3)
        sys_p = rng.randint(1, 128, (24,)).astype(np.int32)
        owner = None
        for i in range(3):
            suf = rng.randint(1, 128, (4,)).astype(np.int32)
            req = fleet.submit(np.concatenate([sys_p, suf]),
                               max_new_tokens=4)
            fleet.run_until_drained()
            req.wait(timeout=60)
            admitted = {w.wid: w.engine.stats()["admitted"]
                        for w in fleet.workers}
            if i == 0:
                owner = max(admitted, key=admitted.get)
            else:
                assert admitted[owner] == i + 1, admitted
        st = fleet.stats()
        assert st["affinity_hits"] == 2
        hit = {w: s["prefix_hit_tokens"]
               for w, s in st["workers"].items()}
        assert hit[owner] > 0
        fleet.close()

    def test_round_robin_alternates(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        p = np.arange(1, 9, dtype=np.int32)
        for _ in range(4):
            fleet.submit(p, max_new_tokens=2)
        counts = [len(w.pending) for w in fleet.workers]
        assert counts == [2, 2]
        fleet.run_until_drained()
        fleet.close()

    def test_submit_with_no_healthy_workers_raises(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=1, engine_kwargs=ENGINE_KW)
        fleet.workers[0].healthy = False
        with pytest.raises(RuntimeError, match="no healthy"):
            fleet.submit(np.arange(1, 5, dtype=np.int32))
        fleet.close()


class TestFailover:
    def test_killed_worker_requests_bitmatch_solo(self):
        """The acceptance bar: kill a worker while its rows are
        mid-decode; every request still completes on the survivor,
        token-for-token identical to an undisturbed solo run (the r7
        recompute-resume path, applied cross-worker)."""
        m = shared_model()
        rng = np.random.RandomState(5)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        reqs, expect = [], []
        for _ in range(4):
            p = rng.randint(1, 128, (10,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=16))
            expect.append(solo_generate(m, p, 16))
        fleet.step()            # admit + first chunk on both workers
        victim = fleet.workers[1]
        assert victim.occupancy > 0     # rows genuinely in flight
        moved = fleet.kill_worker("w1")
        assert moved > 0
        fleet.run_until_drained()
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=60)).reshape(-1),
                e.reshape(-1))
        st = fleet.stats()
        assert st["failovers"] == 1 and st["rerouted"] == moved
        assert st["healthy_workers"] == 1
        # a re-routed resumed request never double-counts TTFT
        assert all(r.trace.ttft is not None for r in reqs)
        fleet.close()

    def test_raising_step_fails_worker_not_fleet(self):
        m = shared_model()
        rng = np.random.RandomState(6)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             engine_kwargs=ENGINE_KW)
        reqs, expect = [], []
        for _ in range(2):
            p = rng.randint(1, 128, (9,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=12))
            expect.append(solo_generate(m, p, 12))
        fleet.step()
        # wedge w1's next decode: the fleet must drain it, not crash
        def boom():
            raise RuntimeError("device lost")
        fleet.workers[1].engine.decode_once = boom
        fleet.run_until_drained()
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=60)).reshape(-1),
                e.reshape(-1))
        assert fleet.workers[1].fail_reason == "drained"
        assert not fleet.workers[1].healthy
        assert fleet.stats()["failovers"] == 1
        fleet.close()

    def test_watchdog_stall_flags_worker_for_failover(self):
        """Drive the per-worker EngineStallWatchdog deterministically:
        a heartbeat that sits still while the worker is busy fires
        once, the on_stall hook marks the worker unhealthy, and the
        next step() re-routes its work."""
        m = shared_model()
        rng = np.random.RandomState(8)
        fleet = ServingFleet(m, n_workers=2, policy="round_robin",
                             stall_s=10.0, engine_kwargs=ENGINE_KW)
        reqs, expect = [], []
        for _ in range(2):
            p = rng.randint(1, 128, (8,)).astype(np.int32)
            reqs.append(fleet.submit(p, max_new_tokens=16))
            expect.append(solo_generate(m, p, 16))
        fleet.step()                        # both workers now busy
        assert fleet.check_watchdogs(now=100.0) == []   # arms baseline
        fired = fleet.check_watchdogs(now=111.0)        # > stall_s idle
        assert [wid for wid, _ in fired] == ["w0", "w1"]
        # both flagged — restore w0 so the fleet has a survivor (the
        # stall was synthetic: its heartbeat never actually wedged)
        fleet.workers[0].healthy = True
        fleet.workers[0].fail_reason = None
        fleet.run_until_drained()
        for r, e in zip(reqs, expect):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=60)).reshape(-1),
                e.reshape(-1))
        assert not fleet.workers[1].healthy
        assert fleet.stats()["failovers"] >= 1
        fleet.close()


class TestWorkerIds:
    def test_engine_stats_worker_id(self):
        m = shared_model()
        from paddle_tpu.inference.serving import DecodeEngine
        eng = DecodeEngine(m, worker_id="w7", **ENGINE_KW)
        assert eng.stats()["worker_id"] == "w7"
        eng2 = DecodeEngine(m, **ENGINE_KW)
        assert eng2.stats()["worker_id"] is None

    def test_batching_server_threads_worker_id(self):
        m = shared_model()
        from paddle_tpu.inference.serving import (BatchingServer,
                                                  GenerationPredictor)
        srv = BatchingServer(GenerationPredictor(m), max_batch=2,
                             continuous=True, worker_id="w3",
                             engine_kwargs=dict(s_max=64, chunk=4,
                                                block_size=8))
        try:
            s = srv.stats()
            assert s["worker_id"] == "w3"
            assert s["engine"]["worker_id"] == "w3"
        finally:
            srv.close()

    def test_fleet_assigns_distinct_ids(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, engine_kwargs=ENGINE_KW)
        ws = fleet.stats()["workers"]
        assert set(ws) == {"w0", "w1"}
        assert all(s["worker_id"] == wid for wid, s in ws.items())
        fleet.close()


class TestAggregatorAndEndpoint:
    def _regs(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.counter("jobs_total", "jobs").inc(3)
        r2.counter("jobs_total", "jobs").inc(4)
        r1.histogram("lat_seconds").observe(0.01)
        r2.histogram("lat_seconds").observe(0.02)
        return r1, r2

    def test_merged_snapshot_sums_workers(self):
        r1, r2 = self._regs()
        agg = MetricsAggregator({"w0": r1, "w1": r2})
        snap = agg.snapshot()
        assert snap["workers"]["w0"]["counters"]["jobs_total"] == 3
        assert snap["fleet"]["counters"]["jobs_total"] == 7
        assert snap["fleet"]["histograms"]["lat_seconds"]["count"] == 2

    def test_prometheus_per_worker_labels_single_type_header(self):
        r1, r2 = self._regs()
        agg = MetricsAggregator({"w0": r1, "w1": r2})
        text = agg.prometheus_text()
        assert 'jobs_total{worker="w0"} 3' in text
        assert 'jobs_total{worker="w1"} 4' in text
        assert text.count("# TYPE jobs_total counter") == 1
        assert text.count("# TYPE lat_seconds histogram") == 1
        assert 'lat_seconds_bucket{worker="w1",le="+Inf"} 1' in text

    def test_type_conflict_raises(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        r1.counter("x")
        r2.gauge("x")
        agg = MetricsAggregator({"w0": r1, "w1": r2})
        with pytest.raises(TypeError, match="conflicting"):
            agg.prometheus_text()

    def test_duplicate_label_raises(self):
        agg = MetricsAggregator({"w0": MetricsRegistry()})
        with pytest.raises(ValueError, match="duplicate"):
            agg.add("w0", MetricsRegistry())

    def test_scrape_endpoint(self):
        r1, r2 = self._regs()
        srv = MetricsHTTPServer(
            MetricsAggregator({"w0": r1, "w1": r2})).start()
        try:
            body = urllib.request.urlopen(srv.url, timeout=10).read()
            text = body.decode()
            assert 'jobs_total{worker="w0"} 3' in text
            js = json.loads(urllib.request.urlopen(
                srv.url + ".json", timeout=10).read())
            assert js["fleet"]["counters"]["jobs_total"] == 7
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/nope", timeout=10)
        finally:
            srv.close()

    def test_fleet_serve_metrics_includes_router(self):
        m = shared_model()
        fleet = ServingFleet(m, n_workers=2, engine_kwargs=ENGINE_KW)
        req = fleet.submit(np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=2)
        fleet.run_until_drained()
        req.wait(timeout=60)
        srv = fleet.serve_metrics()
        try:
            text = urllib.request.urlopen(srv.url,
                                          timeout=10).read().decode()
            assert 'fleet_submitted_total{worker="router"} 1' in text
            assert 'engine_retired_total{worker="w' in text
            assert "# TYPE engine_ttft_seconds histogram" in text
        finally:
            fleet.close()

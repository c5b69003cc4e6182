"""Tensor-parallel sharded decode engine (ISSUE 10): the paged KV
pools shard over the kv-head axis, every paged program lowers through
jit + shard_map, and the decode+verify+prefill-chunk step collapses
into ONE mixed launch. The correctness contract under test is strict
BIT-parity of greedy tokens:

- tp=2 and tp=4 engines vs the unsharded engine on the same seeded
  model, with prefix cache + chunked prefill + spec decode + int8 KV
  each exercised (sharding is device wiring, never a quality trade);
- the engine vs the mp-sharded ``generate()`` path (two independent
  sharded implementations of the same math);
- ``mesh=None`` vs the r14 engine (the default path is untouched);
- a sharded fleet worker after crash + auto-restart vs the solo oracle
  (failover composes with tensor parallelism).

Host-side machinery (allocator, tables, scheduler, QoS) is replicated,
so the allocator-conservation invariant must hold unchanged on a
sharded pool under COW."""

import numpy as np
import pytest

from paddle_tpu.inference.serving import DecodeEngine
from paddle_tpu.inference.sharding import (make_tp_mesh,
                                           validate_tp_config)

from harness import (drain, fresh_model, make_prompts, run_engine,
                     shared_model, solo_generate)


class TestShardedEngineParity:
    def test_tp2_all_features_parity(self):
        """The acceptance oracle: prefix cache + chunked prefill + spec
        decode + int8 KV all ON, tp=2 vs unsharded — greedy tokens
        bit-identical, and the sharded engine provably spends FEWER
        device launches (batched verify + single mixed step)."""
        m = shared_model()
        rng = np.random.RandomState(0)
        shared = rng.randint(1, 128, (10,)).astype(np.int32)
        wave1 = [np.tile(rng.randint(1, 128, (5,)).astype(np.int32), 4),
                 shared]                             # seeds the cache
        wave2 = [np.concatenate([shared, rng.randint(  # hit + COW
                     1, 128, (7,)).astype(np.int32)]),
                 rng.randint(1, 128, (19,)).astype(np.int32)]
        kw = dict(prefix_cache=True, chunked_prefill=True,
                  spec_decode=True, kv_dtype="int8")

        def run(mesh):
            eng = DecodeEngine(m, capacity=4, s_max=64, chunk=4,
                               block_size=8, mesh=mesh, **kw)
            outs = []
            for wave in (wave1, wave2):   # second wave sees the cache
                reqs = [eng.submit(p, max_new_tokens=10) for p in wave]
                outs += drain(eng, reqs)
            return outs, eng

        base, eng0 = run(None)
        outs, eng2 = run(make_tp_mesh(2))
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        s0, s2 = eng0.stats(), eng2.stats()
        assert s2["prefix_hit_tokens"] > 0       # the cache was hit
        assert s2["spec"]["proposed"] > 0        # speculation ran
        assert s2["prefill_chunks"] > 0          # chunked prefill ran
        # the launch-collapse claim, on the engine's own counter
        assert s2["device_calls"] < s0["device_calls"]

    def test_tp4_parity(self):
        """tp=4 over the tiny preset (4 kv heads -> 1 head per shard,
        the deepest split the model admits)."""
        m = shared_model("tiny")
        rng = np.random.RandomState(1)
        prompts = make_prompts(rng, 900, (9, 17))
        base, _ = run_engine(m, prompts, chunked_prefill=True,
                             spec_decode=True)
        outs, eng = run_engine(m, prompts, mesh=make_tp_mesh(4),
                               chunked_prefill=True, spec_decode=True)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        assert eng.stats()["tp_degree"] == 4
        assert eng.stats()["mesh_shape"] == {"tp": 4}

    @pytest.mark.parametrize("tokens", [10, 40])
    def test_tp2_matches_mp_sharded_generate(self, tokens):
        """Two independent sharded implementations of the same math:
        the shard_map engine vs the GSPMD mp-sharded generate() path
        must agree token-for-token (and with the unsharded model). At
        40 tokens the cold prefill's block walk runs two blocks, its
        psum inside the loop."""
        import warnings

        import paddle_tpu.distributed as dist
        m = fresh_model()
        rng = np.random.RandomState(2)
        p = rng.randint(1, 128, (tokens,)).astype(np.int32)
        ref = solo_generate(m, p, 6)
        outs, _ = run_engine(m, [p], max_new=6, mesh=make_tp_mesh(2))
        np.testing.assert_array_equal(outs[0], ref)
        mesh = dist.ProcessMesh(shape=[1, 1, 1, 1, 2],
                                dim_names=["dp", "pp", "sep", "ep",
                                           "mp"])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)  # tiny dims
            dist.shard_model_state(m, mesh)
        mp_out = solo_generate(m, p, 6)
        np.testing.assert_array_equal(outs[0], mp_out)

    def test_mesh_none_keeps_r14_outputs(self):
        """The regression satellite: a default-constructed engine
        (mesh=None) must keep producing exactly the solo greedy
        outputs — the sharding hooks compile to the identical
        programs."""
        m = shared_model()
        rng = np.random.RandomState(3)
        prompts = make_prompts(rng, 128, (7, 12, 20))
        for kw in (dict(),
                   dict(chunked_prefill=True, spec_decode=True,
                        kv_dtype="int8", prefix_cache=True)):
            outs, eng = run_engine(m, prompts, **kw)
            assert eng.mesh is None
            assert eng.stats()["tp_degree"] == 1
            assert "mesh_shape" not in eng.stats()
            for p, o in zip(prompts, outs):
                ref = solo_generate(m, p, 8)
                np.testing.assert_array_equal(o, ref)


class TestValidation:
    def test_mesh_requires_paged(self):
        m = shared_model()
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(m, capacity=2, s_max=64, paged=False,
                         mesh=make_tp_mesh(2))

    def test_axis_name_checked(self):
        m = shared_model()
        with pytest.raises(ValueError, match="tp_axis"):
            DecodeEngine(m, capacity=2, s_max=64,
                         mesh=make_tp_mesh(2, axis="model"))

    def test_divisibility_checked(self):
        m = shared_model()     # debug: 4 heads / 2 kv heads
        with pytest.raises(ValueError, match="kv"):
            DecodeEngine(m, capacity=2, s_max=64, mesh=make_tp_mesh(4))
        cfg = m.config
        validate_tp_config(cfg, 2)      # sanity: tp=2 is fine
        with pytest.raises(ValueError):
            validate_tp_config(cfg, 0)

    def test_mesh_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            make_tp_mesh(64)

    def test_fleet_rejects_oversubscribed_submeshes(self):
        from paddle_tpu.inference.fleet import ServingFleet
        m = shared_model()
        with pytest.raises(ValueError, match="devices"):
            ServingFleet(m, n_workers=5, tp_degree=2,
                         engine_kwargs=dict(capacity=2, s_max=64))


class TestShardedFleet:
    def test_sharded_workers_on_disjoint_submeshes(self):
        """n_workers x tp_degree <= devices: each worker's engine runs
        tp=2 over its own device pair, and routed traffic bit-matches
        the solo unsharded engine."""
        from paddle_tpu.inference.fleet import ServingFleet
        m = shared_model()
        rng = np.random.RandomState(5)
        prompts = make_prompts(rng, 128, (5, 11, 19, 8))
        fleet = ServingFleet(m, n_workers=2, tp_degree=2,
                             engine_kwargs=dict(capacity=2, s_max=64,
                                                chunk=4, block_size=8))
        try:
            devs = [tuple(w.engine.mesh.devices.flat)
                    for w in fleet.workers]
            assert len(set(devs[0]) & set(devs[1])) == 0  # disjoint
            assert fleet.stats()["tp_degree"] == 2
            reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
            fleet.run_until_drained()
            outs = [np.asarray(r.wait(timeout=120)) for r in reqs]
        finally:
            fleet.close()
        solo = []
        for p in prompts:
            o, _ = run_engine(m, [p])
            solo.append(o[0])
        for a, b in zip(outs, solo):
            np.testing.assert_array_equal(a, b)

    def test_sharded_worker_failover_restart_bit_matches_solo(self):
        """ISSUE 9 x ISSUE 10: crash a SHARDED worker mid-flight; the
        fleet fails over, auto-restarts it on the SAME submesh, and
        every request still completes bit-identical to the solo
        oracle."""
        from paddle_tpu.inference.chaos import (FaultEvent,
                                                FaultInjector,
                                                FaultPlan)
        from paddle_tpu.inference.fleet import (RestartPolicy,
                                                ServingFleet)
        m = shared_model()
        rng = np.random.RandomState(6)
        prompts = make_prompts(rng, 128, (10, 10, 10, 10))
        vt = [0.0]
        fleet = ServingFleet(
            m, n_workers=2, policy="round_robin", tp_degree=2,
            engine_kwargs=dict(capacity=2, s_max=64, chunk=4,
                               block_size=8),
            restart=RestartPolicy(auto=True, backoff_base_s=1.0,
                                  clock=lambda: vt[0]))
        FaultInjector(FaultPlan(
            [FaultEvent(1, "worker_crash", "w1")])).install(fleet)
        try:
            old_devs = tuple(fleet.workers[1].engine.mesh.devices.flat)
            reqs = [fleet.submit(p, max_new_tokens=10)
                    for p in prompts]
            fleet.step()
            vt[0] += 0.25
            fleet.step()                    # w1 crashes mid-step
            assert not fleet.workers[1].healthy
            steps = 0
            while not fleet.workers[1].healthy:
                vt[0] += 0.25
                fleet.step()
                steps += 1
                assert steps <= 6, "restart missed the backoff bound"
            # the rebuilt worker reconstructed the SAME submesh
            new_devs = tuple(fleet.workers[1].engine.mesh.devices.flat)
            assert new_devs == old_devs
            assert fleet.workers[1].engine.stats()["tp_degree"] == 2
            fleet.run_until_drained()
            outs = [np.asarray(r.wait(timeout=120)) for r in reqs]
        finally:
            fleet.close()
        for p, o in zip(prompts, outs):
            ref = solo_generate(m, p, 10)
            np.testing.assert_array_equal(o, ref)


class TestShardedPoolInvariants:
    def test_allocator_conservation_under_cow(self):
        """The allocator stays host-side precisely because its
        decisions are device-count-independent: under prefix sharing +
        COW on a SHARDED pool the conservation identity
        (total_allocated - total_freed == used) must hold at every
        step, and the final occupancy must match the unsharded engine
        page-for-page."""
        m = shared_model()
        rng = np.random.RandomState(7)
        shared = rng.randint(1, 128, (10,)).astype(np.int32)  # 8+2:
        #                 the 2-token tail page is the COW trigger
        prompts = [shared,
                   np.concatenate([shared, rng.randint(
                       1, 128, (5,)).astype(np.int32)]),
                   np.concatenate([shared, rng.randint(
                       1, 128, (9,)).astype(np.int32)])]

        def run(mesh):
            eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                               block_size=8, prefix_cache=True,
                               mesh=mesh)
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.admit([])
            for _ in range(10000):
                eng.decode_once()
                st = eng._alloc.stats()
                assert (st["total_allocated"] - st["total_freed"]
                        == st["used"])
                eng.admit([])
                if eng.idle():
                    break
            outs = [np.asarray(r.wait(timeout=120)) for r in reqs]
            return outs, eng

        base, eng0 = run(None)
        outs, eng2 = run(make_tp_mesh(2))
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        st0, st2 = eng0._alloc.stats(), eng2._alloc.stats()
        assert st2["total_allocated"] - st2["total_freed"] \
            == st2["used"]
        # replicated allocator: page accounting identical by value
        for key in ("used", "total_allocated", "total_freed",
                    "high_watermark"):
            assert st2[key] == st0[key], key
        assert eng2.stats()["prefix_hit_tokens"] \
            == eng0.stats()["prefix_hit_tokens"] > 0

    def test_pool_arrays_actually_sharded(self):
        """The tentpole's point: the per-device KV footprint is
        1/tp of the pool (the kv-head axis is split, not copied)."""
        m = shared_model()
        eng = DecodeEngine(m, capacity=2, s_max=64, block_size=8,
                           mesh=make_tp_mesh(2), kv_dtype="int8")
        for arr in (eng._kp, eng._vp):
            shard = arr.addressable_shards[0]
            assert shard.data.shape[2] == arr.shape[2] // 2
        for arr in (eng._kscale, eng._vscale):
            shard = arr.addressable_shards[0]
            assert shard.data.shape[2] == arr.shape[2] // 2

    def test_device_calls_gauge_and_counter(self):
        """Telemetry satellite: engine_device_calls_total counts every
        launch and engine_tp_degree reads the mesh, with the
        worker-labeled snapshot intact."""
        m = shared_model()
        rng = np.random.RandomState(8)
        outs, eng = run_engine(m, make_prompts(rng, 128, (9,)),
                               mesh=make_tp_mesh(2), spec_decode=True)
        snap = eng.metrics.snapshot()
        assert snap["gauges"]["engine_tp_degree"] == 2
        assert snap["counters"]["engine_device_calls_total"] \
            == eng.stats()["device_calls"] > 0

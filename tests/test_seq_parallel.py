"""Sequence-parallel paged attention over the 2-D (seq, tp) mesh
(ISSUE 16): the block-pool PAGE axis shards over ``seq``, each shard
runs the online-softmax over only the pages it owns, and one
partial-accumulator merge (pmax + two psums — ring-attention math on a
flat topology) finishes attention. The correctness contract is strict
BIT-parity of greedy tokens:

- tp x seq SHARDED engines (including tp*seq > n_kv_heads, the
  configuration a kv-head-only mesh cannot legally build) vs the
  unsharded engine on the same seeded arrivals, with prefix cache +
  chunked prefill + spec decode + int8 KV exercised;
- ``seq_degree=1`` must reproduce the 1-D tp engine (and the unsharded
  engine) byte-exactly — the second axis is pure wiring until used.

Kernel-level edge rows (satellite): q_len=0 padding rows stay EXACT
zero through the partial merge, and a final partial page landing on a
shard boundary matches a float64 oracle. Host-side: the striped
allocator keeps table column j in stripe j % seq across every
allocation path, and mesh validation reports ALL violated constraints
at once, naming ``seq`` as the escape hatch past the kv-head cap."""

import numpy as np
import pytest

from paddle_tpu.inference.paged_cache import BlockAllocator
from paddle_tpu.inference.serving import DecodeEngine
from paddle_tpu.inference.sharding import (make_mesh, make_tp_mesh,
                                           validate_mesh_config)

from harness import drain, make_prompts, run_engine, shared_model


class TestSeqParallelParity:
    def test_2x4_beyond_kv_heads_all_features_parity(self):
        """The acceptance oracle: tp=2 x seq=4 = 8 devices on a
        2-kv-head model — four times past the kv-head cap — with
        prefix cache + chunked prefill + spec decode ON, bit-identical
        to the unsharded engine across a cache-seeding wave and a
        hit + COW wave."""
        m = shared_model()                       # debug: 4 heads / 2 kv heads
        rng = np.random.RandomState(0)
        shared = rng.randint(1, 128, (10,)).astype(np.int32)
        wave1 = [np.tile(rng.randint(1, 128, (5,)).astype(np.int32), 4),
                 shared]
        wave2 = [np.concatenate([shared, rng.randint(
                     1, 128, (7,)).astype(np.int32)]),
                 rng.randint(1, 128, (19,)).astype(np.int32)]
        kw = dict(prefix_cache=True, chunked_prefill=True,
                  spec_decode=True)

        def run(mesh):
            eng = DecodeEngine(m, capacity=4, s_max=64, chunk=4,
                               block_size=8, mesh=mesh, **kw)
            outs = []
            for wave in (wave1, wave2):
                reqs = [eng.submit(p, max_new_tokens=10) for p in wave]
                outs += drain(eng, reqs)
            return outs, eng

        base, _ = run(None)
        outs, eng = run(make_mesh(2, 4))
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        s = eng.stats()
        assert s["tp_degree"] == 2
        assert s["seq_degree"] == 4
        assert s["mesh_shape"] == {"seq": 4, "tp": 2}
        assert s["prefix_hit_tokens"] > 0
        assert s["spec"]["proposed"] > 0
        assert s["prefill_chunks"] > 0
        assert s["pool"]["stripes"] == 4

    def test_int8_kv_2d_parity(self):
        """int8 paged KV under page sharding: quantized insert/scatter
        route writes through the owned-page drop path and reads clamp,
        bit-matching the unsharded int8 engine."""
        m = shared_model()
        rng = np.random.RandomState(1)
        prompts = make_prompts(rng, 128, (5, 19, 11))
        base, _ = run_engine(m, prompts, kv_dtype="int8", prefix_cache=True)
        outs, eng = run_engine(m, prompts, mesh=make_mesh(2, 2),
                               kv_dtype="int8", prefix_cache=True)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        assert eng.stats()["seq_degree"] == 2

    def test_seq_only_mesh_parity(self):
        """tp=1, seq=4: page parallelism alone (no kv-head split at
        all) still bit-matches — the two axes are independent."""
        m = shared_model()
        rng = np.random.RandomState(2)
        prompts = make_prompts(rng, 128, (7, 33, 12))
        base, _ = run_engine(m, prompts, chunked_prefill=True,
                             spec_decode=True)
        outs, eng = run_engine(m, prompts, mesh=make_mesh(1, 4),
                               chunked_prefill=True, spec_decode=True)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)
        assert eng.stats()["mesh_shape"] == {"seq": 4, "tp": 1}

    def test_seq1_reproduces_1d_engine(self):
        """seq_degree=1 is the regression satellite: a (1, tp) 2-D mesh
        must produce exactly the 1-D tp engine's outputs (and the
        unsharded engine's), with the unstriped allocator snapshot."""
        m = shared_model()
        rng = np.random.RandomState(3)
        prompts = make_prompts(rng, 128, (9, 17))
        base, _ = run_engine(m, prompts)
        out1d, e1 = run_engine(m, prompts, mesh=make_tp_mesh(2))
        out2d, e2 = run_engine(m, prompts, mesh=make_mesh(2, 1))
        for a, b, c in zip(base, out1d, out2d):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        assert e2.stats()["seq_degree"] == 1
        # stripes=1 keeps the r6 pool-stats shape: no "stripes" key
        assert "stripes" not in e2.stats()["pool"]
        assert e1.stats()["pool"] == e2.stats()["pool"]

    def test_pool_arrays_actually_sharded_2d(self):
        """The tentpole's point: per-device KV footprint is
        1/(tp*seq) of the pool — page axis split over seq, kv-head
        axis split over tp."""
        m = shared_model()
        eng = DecodeEngine(m, capacity=2, s_max=64, block_size=8,
                           mesh=make_mesh(2, 2), kv_dtype="int8")
        for arr in (eng._kp, eng._vp):
            shard = arr.addressable_shards[0]
            assert shard.data.shape[1] == arr.shape[1] // 2
            assert shard.data.shape[2] == arr.shape[2] // 2
        for arr in (eng._kscale, eng._vscale):
            shard = arr.addressable_shards[0]
            assert shard.data.shape[1] == arr.shape[1] // 2
            assert shard.data.shape[2] == arr.shape[2] // 2


class TestSeqKernelEdgeRows:
    """Satellite: mixed-kernel edge rows under page sharding, against
    a float64 oracle built from the same global pools."""

    def _setup(self, rng, n_seq=4, n_blocks=8, bs=4, kvh=2, G=2, hd=8,
               B=2, mb=4):
        kp = rng.standard_normal((n_blocks, bs, kvh, hd)) \
            .astype(np.float32)
        vp = rng.standard_normal((n_blocks, bs, kvh, hd)) \
            .astype(np.float32)
        # striping invariant by construction: column j holds a page
        # from stripe j % n_seq (stripe s owns [2s, 2s+2))
        table = np.zeros((B, mb), np.int32)
        table[0] = [1, 3, 5, 7]
        return kp, vp, table

    def _sharded(self, fn_name, q, kp, vp, table, *lens, n_seq=4,
                 layer=None):
        """``layer``: the entry reads that layer of STACKED pools (the
        decode entry); the pools then ride as layer ``layer`` of a
        stack whose other layers hold noise."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        import paddle_tpu.kernels.paged_attention as pa
        from jax import shard_map
        mesh = Mesh(np.asarray(jax.devices()[:n_seq]), ("seq",))
        kern = getattr(pa, fn_name)
        # the oracle's pools are token-major [N, bs, kvh, hd]; the
        # engine's (and the kernels') keep the kv head ahead of the page
        kp, vp = np.swapaxes(kp, 1, 2), np.swapaxes(vp, 1, 2)
        pool_spec, tail = P("seq"), ()
        if layer is not None:
            noise = np.random.default_rng(9).standard_normal(
                (layer + 2, *kp.shape)).astype(kp.dtype)
            kp, vp = (np.concatenate([noise[:layer], a[None],
                                      noise[layer:]]) for a in (kp, vp))
            pool_spec, tail = P(None, "seq"), (layer,)

        def prog(q, kp, vp, table, *lens):
            return kern(q, kp, vp, table, *lens, *tail, seq_axis="seq",
                        n_seq=n_seq)

        sharded = shard_map(
            prog, mesh=mesh,
            in_specs=(P(), pool_spec, pool_spec, P(),
                      *([P()] * len(lens))),
            out_specs=P())
        return np.asarray(sharded(q, kp, vp, table, *lens))

    def _oracle_row(self, q_row, keys, vals, n_keys):
        """float64 causal-free softmax over the first n_keys keys for
        one [G, hd] query (decode: attends everything resident)."""
        qf = q_row.astype(np.float64)
        k = keys[:n_keys].astype(np.float64)
        v = vals[:n_keys].astype(np.float64)
        s = qf @ k.T / np.sqrt(q_row.shape[-1])
        s -= s.max(axis=-1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=-1, keepdims=True)
        return p @ v

    def test_partial_page_on_shard_boundary_matches_f64(self):
        """seq_len=13 with bs=4: three full pages on shards 0-2 and a
        final 1-token partial page alone on shard 3 — the merge must
        weight that shard's single key exactly like the dense f64
        softmax does."""
        rng = np.random.default_rng(0)
        kp, vp, table = self._setup(rng)
        q = rng.standard_normal((2, 2, 2, 8)).astype(np.float32)
        seq_lens = np.array([13, 0], np.int32)
        out = self._sharded("paged_decode_attention", q, kp, vp,
                            table, seq_lens, layer=1)
        keys = kp[table[0]].reshape(-1, 2, 8)       # [16, kvh, hd]
        vals = vp[table[0]].reshape(-1, 2, 8)
        for n in range(2):                           # kv head
            ref = self._oracle_row(q[0, n], keys[:, n], vals[:, n], 13)
            np.testing.assert_allclose(out[0, n], ref, rtol=2e-5,
                                       atol=2e-6)

    def test_zero_len_rows_stay_exact_zero(self):
        """q_len=0 / kv_len=0 padding rows: every shard's l is 0, so
        the merged accumulator floors at eps over a zero numerator —
        EXACT zeros, not NaN, not denormal noise."""
        rng = np.random.default_rng(1)
        kp, vp, table = self._setup(rng)
        B, T = 2, 4
        q = rng.standard_normal((B, T, 2, 2, 8)).astype(np.float32)
        kv_lens = np.array([13, 0], np.int32)
        q_lens = np.array([4, 0], np.int32)
        out = self._sharded("mixed_paged_attention", q, kp, vp, table,
                            kv_lens, q_lens)
        assert np.all(out[1] == 0.0)
        assert np.all(np.isfinite(out))

    def test_mixed_causal_tail_matches_f64(self):
        """The mixed launch's causal window across the shard-strided
        keys: query t attends keys <= kv_len - q_len + t, including the
        boundary partial page."""
        rng = np.random.default_rng(2)
        kp, vp, table = self._setup(rng)
        q = rng.standard_normal((2, 4, 2, 2, 8)).astype(np.float32)
        kv_lens = np.array([13, 0], np.int32)
        q_lens = np.array([4, 0], np.int32)
        out = self._sharded("mixed_paged_attention", q, kp, vp, table,
                            kv_lens, q_lens)
        keys = kp[table[0]].reshape(-1, 2, 8)
        vals = vp[table[0]].reshape(-1, 2, 8)
        for t in range(4):
            n_vis = 13 - 4 + t + 1
            for n in range(2):
                ref = self._oracle_row(q[0, t, n], keys[:, n],
                                       vals[:, n], n_vis)
                np.testing.assert_allclose(out[0, t, n], ref,
                                           rtol=2e-5, atol=2e-6)


class TestStripedAllocator:
    def test_column_residency_invariant(self):
        """allocate(n, start_col) must hand page i from stripe
        (start_col + i) % stripes — the invariant every strided
        per-shard gather depends on."""
        a = BlockAllocator(16, stripes=4)           # stripe size 4
        for start in (0, 1, 3, 6):
            pages = a.allocate(5, start_col=start)
            assert pages is not None
            for i, p in enumerate(pages):
                assert a.stripe_of(p) == (start + i) % 4
            a.free(pages)
        assert a.conservation_ok

    def test_all_or_nothing_per_stripe(self):
        """A request fails when ITS stripes can't cover it, even with
        free pages elsewhere — exactly what a physically sharded pool
        enforces."""
        a = BlockAllocator(8, stripes=4)    # stripe 0 has 1 page (NULL)
        first = a.allocate(4, start_col=0)  # one page from each stripe
        assert first is not None
        assert a.allocate(1, start_col=0) is None   # stripe 0 empty
        assert a.num_free == 3                      # others untouched
        assert a.shortfall(1, start_col=0) == 1
        assert a.shortfall(1, start_col=1) == 0
        assert a.allocate(1, start_col=1) is not None

    def test_free_returns_to_owning_stripe(self):
        a = BlockAllocator(12, stripes=3)
        pages = a.allocate(6, start_col=2)
        a.free(pages)
        again = a.allocate(6, start_col=2)
        for i, p in enumerate(again):
            assert a.stripe_of(p) == (2 + i) % 3
        # decref path too (prefix sharing)
        a.incref(again[0])
        a.decref(again[0])
        a.decref(again[0])
        assert a.stripe_of(a.allocate(1, start_col=2)[0]) == 2

    def test_stats_and_validation(self):
        assert "stripes" not in BlockAllocator(8).stats()
        assert BlockAllocator(8, stripes=2).stats()["stripes"] == 2
        with pytest.raises(ValueError, match="divisible"):
            BlockAllocator(9, stripes=2)
        with pytest.raises(ValueError, match="NULL"):
            BlockAllocator(8, stripes=8)    # stripe 0 would be empty
        # stripes=1 keeps the full r6 free list (capacity unchanged)
        assert BlockAllocator(8, stripes=1).num_free == 7

    def test_shortfall_unstriped_matches_global(self):
        a = BlockAllocator(8)
        a.allocate(4)
        assert a.shortfall(5) == 2
        assert a.shortfall(3) == 0


class TestValidationAggregate:
    def test_reports_all_violations_in_one_message(self):
        """Satellite: a bad degree lists EVERY violated divisibility
        constraint, not just the first."""
        m = shared_model()                        # 4 heads / 2 kv heads
        with pytest.raises(ValueError) as e:
            validate_mesh_config(m.config, 3)
        msg = str(e.value)
        assert "num_key_value_heads" in msg
        assert "num_attention_heads" in msg
        assert "intermediate_size" in msg

    def test_kv_head_cap_names_seq_escape_hatch(self):
        """tp past the kv-head count points at the 2-D mesh instead of
        dead-ending."""
        m = shared_model()
        with pytest.raises(ValueError, match="seq_degree>1"):
            validate_mesh_config(m.config, 4)
        with pytest.raises(ValueError, match="seq_degree>1"):
            DecodeEngine(m, capacity=2, s_max=64, block_size=8,
                         mesh=make_tp_mesh(4))

    def test_n_blocks_must_divide_over_seq(self):
        m = shared_model()
        with pytest.raises(ValueError, match="n_blocks"):
            validate_mesh_config(m.config, 2, seq=2, n_blocks=7)
        with pytest.raises(ValueError, match="n_blocks"):
            DecodeEngine(m, capacity=2, s_max=64, block_size=8,
                         n_blocks=7, mesh=make_mesh(2, 2))

    def test_mesh_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            make_mesh(4, 4)                 # 16 > the 8 virtual devices
        with pytest.raises(ValueError):
            make_mesh(0, 2)


class TestObservability:
    def test_engine_seq_degree_gauge_and_stats(self):
        """Satellite: stats()/statusz report the full mesh shape per
        engine and the engine_seq_degree gauge reads it live."""
        m = shared_model()
        rng = np.random.RandomState(5)
        outs, eng = run_engine(m, make_prompts(rng, 128, (9,)),
                               mesh=make_mesh(2, 2))
        snap = eng.metrics.snapshot()
        assert snap["gauges"]["engine_tp_degree"] == 2
        assert snap["gauges"]["engine_seq_degree"] == 2
        s = eng.stats()
        assert s["seq_degree"] == 2
        assert s["mesh_shape"] == {"seq": 2, "tp": 2}
        # unsharded engines still report degree 1 (gauge always there)
        _, e0 = run_engine(m, make_prompts(rng, 128, (5,)))
        assert e0.metrics.snapshot()["gauges"]["engine_seq_degree"] == 1


class TestSeqParallelFleet:
    def test_fleet_2d_submesh_parity_and_stats(self):
        """ServingFleet(tp_degree=2, seq_degree=4): the worker builds
        a (4, 2) submesh past the kv-head cap and routed traffic
        bit-matches the solo unsharded engine; fleet stats carry
        seq_degree beside tp_degree."""
        from paddle_tpu.inference.fleet import ServingFleet
        m = shared_model()
        rng = np.random.RandomState(6)
        prompts = make_prompts(rng, 128, (9, 21))
        base, _ = run_engine(m, prompts)
        fl = ServingFleet(m, n_workers=1, tp_degree=2, seq_degree=4,
                          engine_kwargs=dict(capacity=4, s_max=64,
                                             chunk=4, block_size=8))
        try:
            reqs = [fl.submit(p, max_new_tokens=8) for p in prompts]
            for _ in range(3000):
                if fl.step() == 0 and all(not w.pending
                                          for w in fl.workers):
                    break
            outs = [np.asarray(r.wait(timeout=120)) for r in reqs]
            for a, b in zip(base, outs):
                np.testing.assert_array_equal(a, b)
            s = fl.stats()
            assert s["tp_degree"] == 2
            assert s["seq_degree"] == 4
            ws = list(s["workers"].values())[0]
            assert ws["mesh_shape"] == {"seq": 4, "tp": 2}
        finally:
            fl.close()

    def test_fleet_rejects_oversubscribed_2d_submeshes(self):
        from paddle_tpu.inference.fleet import ServingFleet
        m = shared_model()
        with pytest.raises(ValueError, match="seq_degree"):
            ServingFleet(m, n_workers=2, tp_degree=2, seq_degree=4,
                         engine_kwargs=dict(capacity=2, s_max=64))

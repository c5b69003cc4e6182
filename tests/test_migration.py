"""Cross-worker KV page transplant + prefill/decode disaggregation
(ISSUE 14): the transplant primitive's conservation and fidelity
contracts (fp and int8 pools, tp-sharded pools on shared and disjoint
placements), its failure modes (stale chain, full destination), and
the fleet paths built on it — warm-prefix migration on route and the
role-split handoff — each pinned to strict BIT-parity of greedy
tokens against the solo oracle. Migration disabled (the default) must
leave the r14 fleet byte-identical."""

import numpy as np
import pytest

from paddle_tpu.inference.fleet import ServingFleet
from paddle_tpu.inference.migration import (MigrationResult,
                                            transplant_prefix)
from paddle_tpu.inference.serving import DecodeEngine

from harness import ENGINE_KW, drive, shared_model, solo_generate


def _run_one(eng, p, mn=8):
    r = eng.submit(p, max_new_tokens=mn)
    drive(eng)
    return np.asarray(r.wait(timeout=120)).reshape(-1)


def _conserved(*engines):
    for e in engines:
        assert e._alloc.conservation_ok, \
            f"conservation broken on {e.worker_id}: {e._alloc.stats()}"


class TestTransplantPrimitive:
    def test_warm_replay_bit_identical(self):
        """A transplanted chain serves the destination engine's own
        admission: the replayed prompt matches the migrated pages and
        decodes bit-identically to the source run (and the oracle)."""
        m = shared_model()
        rng = np.random.RandomState(3)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        res = transplant_prefix(src, dst, out)
        assert res.reason == "ok" and res.moved
        assert res.pages == len(res.pages_dst) == len(res.pages_src)
        assert res.tokens == res.pages * ENGINE_KW["block_size"]
        assert res.fused          # same default device placement
        _conserved(src, dst)
        # destination admission must HIT the transplanted chain
        out2 = _run_one(dst, p)
        np.testing.assert_array_equal(out, out2)
        np.testing.assert_array_equal(out, solo_generate(m, p, 8).reshape(-1))
        assert dst._cache.hit_tokens > 0
        _conserved(src, dst)

    def test_source_chain_stays_published(self):
        """Migration COPIES — the source keeps serving its own chain
        warm afterwards (this is replication, not theft)."""
        m = shared_model()
        rng = np.random.RandomState(4)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        transplant_prefix(src, dst, out)
        hits0 = src._cache.hit_tokens
        out2 = _run_one(src, p)
        np.testing.assert_array_equal(out, out2)
        assert src._cache.hit_tokens > hits0

    def test_int8_scale_fidelity(self):
        """int8 pools move codes AND per-page scales: destination
        pages carry the source's running-max scales bit-exactly, not
        the eps floor a fresh allocation would have (the drain-before-
        copy ordering under test)."""
        m = shared_model()
        rng = np.random.RandomState(5)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(m, kv_dtype="int8", worker_id="src",
                           **ENGINE_KW)
        dst = DecodeEngine(m, kv_dtype="int8", worker_id="dst",
                           **ENGINE_KW)
        out = _run_one(src, p)
        res = transplant_prefix(src, dst, out)
        assert res.reason == "ok"
        from paddle_tpu.kernels.paged_attention import KV_SCALE_EPS
        for s_arr, d_arr in ((src._kscale, dst._kscale),
                             (src._vscale, dst._vscale)):
            s = np.asarray(s_arr)[:, res.pages_src]
            d = np.asarray(d_arr)[:, res.pages_dst]
            np.testing.assert_array_equal(s, d)
            # a drain-after-copy bug would leave every lane at eps
            assert not np.all(d == np.float32(KV_SCALE_EPS))
        out2 = _run_one(dst, p)
        np.testing.assert_array_equal(out, out2)
        _conserved(src, dst)

    def test_tp2_same_mesh_fused(self):
        """tp=2 pools over the SAME submesh ride the fused launch (the
        page axis is unsharded, so the gather/scatter is
        spec-preserving) and replay bit-identically."""
        import jax
        from paddle_tpu.inference.sharding import make_tp_mesh
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        m = shared_model()
        rng = np.random.RandomState(6)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        mesh = make_tp_mesh(2, devices=jax.devices()[:2])
        src = DecodeEngine(m, mesh=mesh, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, mesh=mesh, worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        res = transplant_prefix(src, dst, out)
        assert res.reason == "ok" and res.fused
        out2 = _run_one(dst, p)
        np.testing.assert_array_equal(out, out2)
        np.testing.assert_array_equal(out, solo_generate(m, p, 8).reshape(-1))
        _conserved(src, dst)

    def test_tp2_disjoint_submeshes_host_bounce(self):
        """Fleet-shaped placement: two tp=2 workers on DISJOINT
        submeshes. The copy bounces through host (the in-process
        stand-in for the multi-host ICI/RDMA hop) and still replays
        bit-identically."""
        import jax
        from paddle_tpu.inference.sharding import make_tp_mesh
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        m = shared_model()
        rng = np.random.RandomState(7)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(
            m, mesh=make_tp_mesh(2, devices=jax.devices()[0:2]),
            worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(
            m, mesh=make_tp_mesh(2, devices=jax.devices()[2:4]),
            worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        res = transplant_prefix(src, dst, out)
        assert res.reason == "ok" and not res.fused
        out2 = _run_one(dst, p)
        np.testing.assert_array_equal(out, out2)
        _conserved(src, dst)

    def test_racing_eviction_yields_stale(self):
        """The directory-staleness race: the chain was evicted between
        the caller's hint and the transplant. The owner's match
        refutes the hint — reason ``stale``, ZERO allocator movement
        on either end (one cold prefill, never a wrong answer)."""
        m = shared_model()
        rng = np.random.RandomState(8)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        src._cache.evict(10**6)         # the race, made deterministic
        before = (src._alloc.stats(), dst._alloc.stats())
        res = transplant_prefix(src, dst, out)
        assert res.reason == "stale" and not res.moved
        assert (src._alloc.stats(), dst._alloc.stats()) == before
        _conserved(src, dst)

    def test_pinned_chain_survives_eviction(self):
        """Mid-migration safety: pages pinned by the transplant's own
        match are refcount>=2, so a concurrent evict sweep cannot free
        them (evict only drops refcount-1 childless nodes)."""
        m = shared_model()
        rng = np.random.RandomState(9)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        out = _run_one(src, p)
        mm = src._cache.match([int(t) for t in out], len(out) - 1)
        assert mm.pages
        src._cache.evict(10**6)         # sweeps everything unpinned
        for pg in mm.pages:             # pinned pages still allocated
            assert src._alloc.refcount(pg) >= 1
        src._cache.release(mm)
        src._cache.release_cow(mm)
        _conserved(src)

    def test_dst_full_aborts_clean(self):
        """All-or-nothing: a destination pool that cannot fund the
        chain (even after its own LRU eviction) aborts with nothing
        changed on either allocator."""
        m = shared_model()
        rng = np.random.RandomState(10)
        p = rng.randint(1, 128, (30,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        kw = dict(ENGINE_KW, n_blocks=3)    # 2 allocatable pages
        dst = DecodeEngine(m, worker_id="dst", **kw)
        out = _run_one(src, p)
        before = (src._alloc.stats(), dst._alloc.stats())
        res = transplant_prefix(src, dst, out)   # needs 4 pages
        assert res.reason == "dst_full" and not res.moved
        assert (src._alloc.stats(), dst._alloc.stats()) == before
        _conserved(src, dst)

    def test_budget_caps_pages(self):
        m = shared_model()
        rng = np.random.RandomState(11)
        p = rng.randint(1, 128, (30,)).astype(np.int32)
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, worker_id="dst", **ENGINE_KW)
        out = _run_one(src, p)
        res = transplant_prefix(src, dst, out, max_pages=2)
        assert res.reason == "ok" and res.pages == 2
        _conserved(src, dst)

    def test_no_chain_and_same_engine(self):
        m = shared_model()
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        dst = DecodeEngine(m, worker_id="dst", **ENGINE_KW)
        assert transplant_prefix(src, dst, [1, 2, 3]).reason \
            == "no_chain"                   # under one full page
        assert transplant_prefix(src, src, list(range(20))).reason \
            == "no_chain"
        assert transplant_prefix(
            src, dst, list(range(20)), max_pages=0).reason == "no_chain"

    def test_layout_mismatch_raises(self):
        m = shared_model()
        src = DecodeEngine(m, worker_id="src", **ENGINE_KW)
        kw = dict(ENGINE_KW, block_size=16)
        dst = DecodeEngine(m, worker_id="dst", **kw)
        with pytest.raises(ValueError):
            transplant_prefix(src, dst, list(range(32)))
        q = DecodeEngine(m, kv_dtype="int8", worker_id="q",
                         **ENGINE_KW)
        with pytest.raises(ValueError):
            transplant_prefix(src, q, list(range(32)))

    def test_result_shape(self):
        r = MigrationResult()
        assert r.reason == "ok" and r.pages == 0 and not r.moved


class TestFleetRouteMigration:
    def _warm(self, fleet, p, mn=8):
        r = fleet.submit(p, max_new_tokens=mn)
        fleet.run_until_drained()
        return np.asarray(r.wait(timeout=120)).reshape(-1)

    def test_route_migration_bit_identical(self):
        """A directory hit that loses the route to its own load
        penalty moves the chain to the winner; the re-submitted prompt
        decodes bit-identically warm."""
        m = shared_model()
        rng = np.random.RandomState(12)
        A = rng.randint(1, 128, (24,)).astype(np.int32)
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             migration_budget_pages=8,
                             load_penalty=100.0)
        out1 = self._warm(fleet, A)
        # pile load on the cached worker so affinity loses the route
        for n in (16, 16, 16):
            fleet.submit(rng.randint(1, 128, (n,)).astype(np.int32),
                         max_new_tokens=4)
        r2 = fleet.submit(A, max_new_tokens=8)
        st = fleet.stats()
        assert st["migrations"] >= 1
        assert st["migrated_pages"] >= 1
        fleet.run_until_drained()
        out2 = np.asarray(r2.wait(timeout=120)).reshape(-1)
        np.testing.assert_array_equal(out1, out2)
        np.testing.assert_array_equal(out1, solo_generate(m, A, 8).reshape(-1))
        ev = [e for e in fleet.flight.snapshot()["events"]
              if e.get("kind") == "kv_migrated"]
        assert ev and ev[0]["pages"] >= 1
        for w in fleet.workers:
            assert w.engine._alloc.conservation_ok
        fleet.close()

    def test_stale_hint_counted_and_survived(self):
        """A stale directory hint (owner evicted since on_insert) is
        refuted by the owner's match: the stale-hint counter moves and
        the request cold-prefills correctly on its routed worker."""
        m = shared_model()
        rng = np.random.RandomState(13)
        A = rng.randint(1, 128, (24,)).astype(np.int32)
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             migration_budget_pages=8,
                             load_penalty=100.0)
        # plant a hint the owner does not hold (hint-only consistency:
        # the directory may always run ahead of the caches)
        fleet.directory.on_insert("w0", [int(t) for t in A])
        for n in (16, 16, 16):
            fleet.submit(rng.randint(1, 128, (n,)).astype(np.int32),
                         max_new_tokens=4)
        r = fleet.submit(A, max_new_tokens=8)
        st = fleet.stats()
        assert st["stale_hints"] >= 1
        assert st["migrations"] == 0
        fleet.run_until_drained()
        out = np.asarray(r.wait(timeout=120)).reshape(-1)
        np.testing.assert_array_equal(out, solo_generate(m, A, 8).reshape(-1))
        fleet.close()

    def test_migration_off_is_baseline(self):
        """Default knobs (roles=None, migration_budget_pages unset)
        keep the r14 fleet: zero migrations, zero migration debt, and
        bit-identical outputs vs the oracle."""
        m = shared_model()
        rng = np.random.RandomState(14)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (24, 18, 30, 12)]
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW))
        reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        fleet.run_until_drained()
        st = fleet.stats()
        assert st["migrations"] == 0 and st["migrated_pages"] == 0
        assert st["roles"] is None
        for w in fleet.workers:
            assert w.engine._mig_debt == 0
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=120)).reshape(-1),
                solo_generate(m, p, 8).reshape(-1))
        fleet.close()

    def test_roles_validation(self):
        m = shared_model()
        with pytest.raises(ValueError):
            ServingFleet(m, n_workers=2, roles=("prefill",))
        with pytest.raises(ValueError):
            ServingFleet(m, n_workers=2, roles=("prefill", "oracle"))
        with pytest.raises(ValueError):
            ServingFleet(m, n_workers=2, roles=("decode", "decode"))


class TestRoleSplitFleet:
    def test_role_split_bit_identical(self):
        """The full disaggregated path: prompts prefill on the prefill
        worker (forced chunked), finished rows hand off over the
        transplant, decode workers resume — and every output matches
        the solo oracle bit-for-bit, with the ``migrated`` hop on the
        traces and conservation on every pool."""
        m = shared_model()
        rng = np.random.RandomState(15)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (24, 18, 30, 12)]
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             roles=("prefill", "decode"))
        assert fleet.workers[0].role == "prefill"
        assert fleet.workers[0].engine.chunked_prefill
        assert fleet.workers[1].role == "decode"
        reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        fleet.run_until_drained()
        st = fleet.stats()
        assert st["migrations"] >= 1
        assert st["roles"] == {"w0": "prefill", "w1": "decode"}
        hopped = 0
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                np.asarray(r.wait(timeout=120)).reshape(-1),
                solo_generate(m, p, 8).reshape(-1))
            hops = [h for h in getattr(r.trace, "hops", [])
                    if h.get("reason") == "migrated"]
            hopped += bool(hops)
        assert hopped >= 1
        for w in fleet.workers:
            assert w.engine._alloc.conservation_ok
        fleet.close()

    def test_role_split_repeat_bit_for_bit(self):
        """Same seed, run twice: the disaggregated fleet is
        deterministic end to end."""
        m = shared_model()

        def run():
            rng = np.random.RandomState(16)
            prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                       for n in (26, 14, 22)]
            fleet = ServingFleet(m, n_workers=2,
                                 engine_kwargs=dict(ENGINE_KW),
                                 roles=("prefill", "decode"))
            reqs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
            fleet.run_until_drained()
            outs = [np.asarray(r.wait(timeout=120)).reshape(-1)
                    for r in reqs]
            st = fleet.stats()
            fleet.close()
            return outs, st["migrations"]

        o1, m1 = run()
        o2, m2 = run()
        assert m1 == m2
        for a, b in zip(o1, o2):
            np.testing.assert_array_equal(a, b)

    def test_prefill_worker_down_degrades(self):
        """With the only prefill worker dead, the router falls back to
        any healthy worker — a degraded fleet beats a dead one."""
        m = shared_model()
        rng = np.random.RandomState(17)
        p = rng.randint(1, 128, (20,)).astype(np.int32)
        fleet = ServingFleet(m, n_workers=2,
                             engine_kwargs=dict(ENGINE_KW),
                             roles=("prefill", "decode"))
        fleet.kill_worker("w0")
        r = fleet.submit(p, max_new_tokens=8)
        fleet.run_until_drained()
        np.testing.assert_array_equal(
            np.asarray(r.wait(timeout=120)).reshape(-1),
            solo_generate(m, p, 8).reshape(-1))
        fleet.close()

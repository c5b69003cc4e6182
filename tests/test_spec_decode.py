"""Self-speculative decoding + int8 paged KV (ISSUE 8).

Tentpole coverage: the n-gram drafter's contract (deterministic, limit-
clamped, recency-preferring), the verify/accept step's correctness
oracle (spec ON outputs bit-match plain greedy decode — including under
chunked prefill and preemption mid-flight), implicit KV rollback
accounting (allocator conservation under reject-heavy load), and the
int8 quantized pool: round-trip error bounds, the running-max ratio-1.0
no-op, pool-edge scale indexing, Pallas-interpret vs XLA-reference
bit-exactness, and engine-level greedy token parity with fp KV.

Satellite coverage: spec lifecycle/metric accounting (proposed/accepted
counters, accept-length histogram, accept-rate gauge, spec_verify trace
marks) and multi-token TPOT accounting (decode_chunk marks carry
n_tokens; served_tokens counts emissions, not steps).
"""

import numpy as np
import pytest

from paddle_tpu.inference.spec_decode import NgramDrafter

from harness import drive, shared_model, solo_generate


def _run(m, prompts, max_new, iters=600, **kw):
    from paddle_tpu.inference.serving import DecodeEngine, _Request
    eng = DecodeEngine(m, **kw)
    reqs = [_Request(p, max_new) for p in prompts]
    drive(eng, list(reqs), iters=iters)
    return eng, reqs, [r.wait(timeout=1) for r in reqs]


class TestNgramDrafter:
    def test_periodic_tail_drafts_the_continuation(self):
        d = NgramDrafter(max_draft=4)
        ctx = np.asarray([5, 6, 7, 5, 6, 7, 5, 6], np.int32)
        # suffix [7, 5, 6] matched at position 2 -> continue with the
        # tokens that followed it (everything resident past the match)
        np.testing.assert_array_equal(d.propose(ctx), [7, 5, 6])

    def test_no_match_returns_empty(self):
        d = NgramDrafter(max_draft=4)
        assert d.propose(np.arange(1, 9, dtype=np.int32)).size == 0

    def test_limit_clamps_draft_length(self):
        d = NgramDrafter(max_draft=4)
        ctx = np.asarray([5, 6, 7, 5, 6, 7, 5, 6], np.int32)
        assert d.propose(ctx, limit=2).size <= 2
        assert d.propose(ctx, limit=0).size == 0

    def test_deterministic_and_pure(self):
        d = NgramDrafter(max_draft=4)
        rng = np.random.RandomState(11)
        for _ in range(50):
            ctx = rng.randint(0, 8, (rng.randint(2, 40),)).astype(
                np.int32)
            before = ctx.copy()
            a, b = d.propose(ctx), d.propose(ctx)
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(ctx, before)  # no mutation
            assert a.size <= 4 and a.dtype == np.int32

    def test_drafts_only_tokens_seen_in_context(self):
        d = NgramDrafter(max_draft=4)
        rng = np.random.RandomState(12)
        for _ in range(50):
            ctx = rng.randint(0, 6, (rng.randint(2, 30),)).astype(
                np.int32)
            assert set(d.propose(ctx)) <= set(ctx.tolist())


class TestSpecEngine:
    def test_knob_validation(self):
        from paddle_tpu.inference.serving import DecodeEngine
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(shared_model(), paged=False, spec_decode=True)
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(shared_model(), paged=False, kv_dtype="int8")
        with pytest.raises(ValueError, match="kv_dtype"):
            DecodeEngine(shared_model(), kv_dtype="fp16")
        with pytest.raises(ValueError, match="spec_max_draft"):
            DecodeEngine(shared_model(), spec_decode=True, spec_max_draft=0)

    def test_spec_bit_matches_greedy(self):
        """The tentpole oracle: spec ON emits EXACTLY the plain greedy
        tokens (every accepted token is the verify program's argmax),
        on a mix of draft-friendly periodic prompts and draft-hostile
        random ones — and actually accepts drafts on the former."""
        m = shared_model()
        rng = np.random.RandomState(7)
        prompts = [np.tile(rng.randint(1, 128, (8,)).astype(np.int32), 4),
                   rng.randint(1, 128, (17,)).astype(np.int32),
                   np.tile(rng.randint(1, 128, (6,)).astype(np.int32), 5)]
        solo = [solo_generate(m, p, 16) for p in prompts]
        kw = dict(capacity=4, s_max=128, chunk=4, block_size=16)
        _, _, plain = _run(m, prompts, 16, **kw)
        eng, reqs, spec = _run(m, prompts, 16, spec_decode=True, **kw)
        for s, a, b in zip(solo, plain, spec):
            np.testing.assert_array_equal(a, s)
            np.testing.assert_array_equal(b, s)
        st = eng.stats()["spec"]
        assert st["proposed"] > 0 and st["accepted"] > 0
        assert st["verify_steps"] > 0
        assert 1.0 <= st["tokens_per_step"] <= eng.spec_max_draft + 1
        # lifecycle: every verify step left a spec_verify trace mark
        assert sum(r.trace.count("spec_verify") for r in reqs) \
            == st["verify_steps"]

    def test_spec_with_chunked_prefill_bit_matches(self):
        m = shared_model()
        rng = np.random.RandomState(8)
        prompts = [np.tile(rng.randint(1, 128, (7,)).astype(np.int32), 5),
                   rng.randint(1, 128, (29,)).astype(np.int32)]
        kw = dict(capacity=4, s_max=128, chunk=4, block_size=16)
        _, _, plain = _run(m, prompts, 12, **kw)
        _, _, spec = _run(m, prompts, 12, spec_decode=True,
                          chunked_prefill=True, **kw)
        for a, b in zip(plain, spec):
            np.testing.assert_array_equal(a, b)

    def test_spec_survives_preemption(self):
        """A pool small enough that decode growth must preempt rows:
        preempted-mid-flight spec rows re-queue with their full emitted
        history and the final outputs still bit-match solo greedy."""
        m = shared_model()
        rng = np.random.RandomState(9)
        prompts = [rng.randint(1, 128, (24,)).astype(np.int32)
                   for _ in range(3)]
        eng, reqs, out = _run(
            m, prompts, 16, capacity=3, s_max=64, chunk=4,
            block_size=8, n_blocks=13, spec_decode=True, iters=2000)
        for p, o in zip(prompts, out):
            np.testing.assert_array_equal(o, solo_generate(m, p, 16))
        assert eng.stats()["preempted"] > 0   # the scenario happened

    def test_rollback_conserves_allocator_accounting(self):
        """Rejected drafts roll back by lens rewind — no page churn.
        Under a reject-heavy random workload the allocator conservation
        invariant holds and the pool drains to empty at idle."""
        m = shared_model()
        rng = np.random.RandomState(10)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (9, 17, 23, 31)]
        eng, _, out = _run(m, prompts, 12, capacity=4, s_max=96,
                           chunk=4, block_size=16, prefix_cache=False,
                           spec_decode=True)
        assert all(o is not None for o in out)
        a = eng._alloc
        assert a.total_allocated - a.total_freed == a.in_use == 0

    def test_qos_accounting_reproduces_bit_for_bit(self):
        """Acceptance: accept-rate and per-tenant token accounting
        reproduce EXACTLY across a repeat of the same seeded two-tenant
        workload — speculation adds no nondeterminism (tenants are
        charged accepted tokens only, and the accept chain is a pure
        function of the weights and prompts)."""
        from paddle_tpu.inference.qos import QoSPolicy, TenantPolicy
        from paddle_tpu.inference.serving import DecodeEngine
        m = shared_model()
        rng = np.random.RandomState(30)
        prompts = [np.tile(rng.randint(1, 128, (6,)).astype(np.int32),
                           4) for _ in range(4)]

        def once():
            qos = QoSPolicy([
                TenantPolicy("a", rate=1e6, burst=1e6, weight=2.0),
                TenantPolicy("b", rate=1e6, burst=1e6)])
            eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                               block_size=16, qos=qos, spec_decode=True)
            reqs = [eng.submit(p, max_new_tokens=12,
                               tenant="ab"[i % 2])
                    for i, p in enumerate(prompts)]
            drive(eng, [])
            outs = [np.asarray(r.wait(timeout=5)) for r in reqs]
            return eng.stats()["spec"], qos.stats(), outs

        s1, q1, o1 = once()
        s2, q2, o2 = once()
        assert s1 == s2
        assert q1 == q2
        for a, b in zip(o1, o2):
            np.testing.assert_array_equal(a, b)

    def test_served_tokens_counts_emissions_not_steps(self):
        """Multi-token TPOT fix: decode_chunk marks carry n_tokens, so
        a request's served_tokens equals its emitted decode tokens
        (max_new minus the prefill-produced first token) in BOTH the
        plain chunked path and the spec path."""
        m = shared_model()
        rng = np.random.RandomState(13)
        p = np.tile(rng.randint(1, 128, (8,)).astype(np.int32), 4)
        kw = dict(capacity=2, s_max=128, chunk=4, block_size=16)
        _, (rp,), _ = _run(m, [p], 16, **kw)
        _, (rs,), _ = _run(m, [p], 16, spec_decode=True, **kw)
        assert rp.trace.served_tokens == 15
        assert rs.trace.served_tokens == 15
        # spec took fewer decode marks for the same tokens
        assert rs.trace.count("decode_chunk") \
            <= rp.trace.count("decode_chunk") * 4


class TestInt8PagedKV:
    def test_token_insert_round_trip_bound(self):
        """One quantized write: dequant error per element is at most
        half the per-(page, head) scale step."""
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import KV_SCALE_EPS
        from paddle_tpu.models.paged_stack import _quantized_token_insert
        rng = np.random.RandomState(20)
        tok = rng.randn(2, 3, 8).astype(np.float32)
        pool = jnp.zeros((2, 4, 3, 16, 8), jnp.int8)
        scales = jnp.full((2, 4, 3), KV_SCALE_EPS, jnp.float32)
        page = jnp.asarray([1, 2], jnp.int32)
        off = jnp.asarray([0, 5], jnp.int32)
        pool, scales = _quantized_token_insert(
            pool, scales, 1, page, off, jnp.asarray(tok))
        pool, scales = np.asarray(pool)[1], np.asarray(scales)[1]
        for b, (pg, o) in enumerate([(1, 0), (2, 5)]):
            deq = pool[pg, :, o].astype(np.float32) * scales[pg][:, None]
            step = scales[pg][:, None]
            assert np.all(np.abs(deq - tok[b]) <= 0.5 * step + 1e-7)
            # scale is exactly amax/127 for a fresh page
            np.testing.assert_allclose(
                scales[pg], np.abs(tok[b]).max(-1) / 127.0, rtol=1e-6)

    def test_running_max_noop_keeps_codes_bit_identical(self):
        """Inserting a SMALLER token into a page must not perturb the
        resident codes: ratio old/new == 1.0 exactly, round(q*1.0)==q."""
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import KV_SCALE_EPS
        from paddle_tpu.models.paged_stack import _quantized_token_insert
        rng = np.random.RandomState(21)
        big = (rng.randn(1, 2, 8) * 4).astype(np.float32)
        small = (rng.randn(1, 2, 8) * 0.01).astype(np.float32)
        pool = jnp.zeros((1, 3, 2, 16, 8), jnp.int8)
        scales = jnp.full((1, 3, 2), KV_SCALE_EPS, jnp.float32)
        page = jnp.asarray([1], jnp.int32)
        pool, scales = _quantized_token_insert(
            pool, scales, 0, page, jnp.asarray([0], jnp.int32),
            jnp.asarray(big))
        before = np.asarray(pool)[0, 1, :, 0].copy()
        s_before = np.asarray(scales)[0, 1].copy()
        pool, scales = _quantized_token_insert(
            pool, scales, 0, page, jnp.asarray([1], jnp.int32),
            jnp.asarray(small))
        np.testing.assert_array_equal(np.asarray(pool)[0, 1, :, 0],
                                      before)
        np.testing.assert_array_equal(np.asarray(scales)[0, 1], s_before)

    def test_gather_dequant_pool_edge_scale_indexing(self):
        """Each block dequantizes with ITS page's per-head scale — pin
        the indexing with the first and LAST allocatable page carrying
        distinct per-head scales over all-ones codes."""
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import (
            KV_SCALE_EPS, gather_pages_dequant)
        N, bs, kvh, hd = 6, 8, 2, 4
        pages = jnp.ones((N, kvh, bs, hd), jnp.int8)
        scales = np.full((N, kvh), KV_SCALE_EPS, np.float32)
        scales[1] = [2.0, 3.0]
        scales[N - 1] = [5.0, 7.0]
        table = jnp.asarray([[1, N - 1]], jnp.int32)
        g = np.asarray(gather_pages_dequant(
            pages, table, jnp.asarray(scales)))
        assert g.shape == (1, 2 * bs, kvh, hd)
        np.testing.assert_array_equal(g[0, :bs, 0], 2.0)
        np.testing.assert_array_equal(g[0, :bs, 1], 3.0)
        np.testing.assert_array_equal(g[0, bs:, 0], 5.0)
        np.testing.assert_array_equal(g[0, bs:, 1], 7.0)

    def test_pallas_interpret_matches_xla_reference_bit_exact(self):
        """The int8 Pallas kernel body and the XLA reference share one
        block-update helper, so interpret mode must agree BIT-EXACTLY
        (assert_array_equal, not allclose)."""
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import (
            _paged_attn_reference_int8, paged_attention_pallas)
        rng = np.random.RandomState(22)
        B, kvh, G, hd, N, bs = 3, 2, 2, 16, 8, 16
        q = jnp.asarray(rng.randn(B, kvh, G, hd).astype(np.float32))
        kp = jnp.asarray(
            rng.randint(-127, 128, (1, N, kvh, bs, hd)).astype(np.int8))
        vp = jnp.asarray(
            rng.randint(-127, 128, (1, N, kvh, bs, hd)).astype(np.int8))
        ks = jnp.asarray(rng.rand(1, N, kvh).astype(np.float32) * 0.1)
        vs = jnp.asarray(rng.rand(1, N, kvh).astype(np.float32) * 0.1)
        tables = jnp.asarray(rng.permutation(np.arange(1, 7))[:6]
                             .reshape(3, 2).astype(np.int32))
        lens = jnp.asarray([5, 16, 23], jnp.int32)
        out_k = paged_attention_pallas(q, kp, vp, tables, lens, 0,
                                       interpret=True,
                                       kv_scales=(ks, vs))
        out_r = _paged_attn_reference_int8(q, kp, vp, tables, lens, 0,
                                           (ks, vs))
        np.testing.assert_array_equal(np.asarray(out_k),
                                      np.asarray(out_r))

    def test_int8_greedy_tokens_match_fp(self):
        """Engine-level acceptance: on the seeded debug model, int8 KV
        changes logits by less than the greedy argmax margin — emitted
        tokens are identical to the fp pool (prefix cache and chunked
        prefill on, to exercise COW scale copies and the scatter path)."""
        m = shared_model()
        rng = np.random.RandomState(23)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (8, 21, 33)]
        kw = dict(capacity=4, s_max=96, chunk=4, block_size=16)
        _, _, fp = _run(m, prompts, 10, **kw)
        _, _, q8 = _run(m, prompts, 10, kv_dtype="int8", **kw)
        _, _, q8c = _run(m, prompts, 10, kv_dtype="int8",
                         chunked_prefill=True, **kw)
        for a, b, c in zip(fp, q8, q8c):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    def test_recycled_page_scale_resets(self):
        """A page freed by one request and recycled by the next must
        drop the previous tenant's running-max scale before the next
        write — otherwise scales only ever coarsen. Pin the drain
        contract directly on a live int8 engine."""
        import jax.numpy as jnp
        import numpy as _np
        from paddle_tpu.kernels.paged_attention import KV_SCALE_EPS
        from paddle_tpu.inference.serving import DecodeEngine
        eng = DecodeEngine(shared_model(), capacity=2, s_max=64, chunk=4,
                           block_size=8, prefix_cache=False,
                           kv_dtype="int8")
        assert eng._alloc.track_allocations
        (pg,) = eng._alloc.allocate(1)
        eng._drain_scale_resets()           # fresh hand-out: at floor
        # a tenant wrote outliers into the page...
        eng._kscale = eng._kscale.at[:, pg].set(9.0)
        eng._vscale = eng._vscale.at[:, pg].set(9.0)
        eng._alloc.free([pg])
        again = eng._alloc.allocate(1)      # LIFO: same page comes back
        assert again == [pg]
        eng._drain_scale_resets()           # ...which must not leak
        _np.testing.assert_array_equal(
            _np.asarray(eng._kscale[:, pg]), _np.float32(KV_SCALE_EPS))
        _np.testing.assert_array_equal(
            _np.asarray(eng._vscale[:, pg]), _np.float32(KV_SCALE_EPS))
        # fp engines never track, so the hand-out log stays empty
        eng_fp = DecodeEngine(shared_model(), capacity=2, s_max=64, chunk=4,
                              block_size=8, prefix_cache=False)
        eng_fp._alloc.allocate(2)
        assert eng_fp._alloc.drain_allocated() == []

"""Paged KV-cache serving stack: ragged paged-attention kernel parity
(interpret mode on CPU; real Mosaic on TPU), block allocator behavior,
and the paged DecodeEngine's never-reset continuous batching."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harness import (drive, make_prompts, run_engine, shared_model,
                     solo_generate)


N_LAYERS = 3


def _random_paged(seed=0, kvh=2, G=4, hd=128, bs=16, max_blocks=4,
                  lens=(37, 5, 64), inactive=()):
    """Random STACKED block pools [L, N, kvh, bs, hd] (every layer its
    own values) + tables with ragged per-row lengths (one row
    mid-block, one tiny, one exactly on a block boundary). Rows named
    in ``inactive`` are the engine's empty lanes: an all-NULL table and
    the length 1 the decode step hands the kernel for them."""
    rng = np.random.RandomState(seed)
    lens = np.asarray(lens, np.int32)
    B = len(lens)
    n_blocks = 1 + int(sum(-(-int(n) // bs) for n in lens))
    q = rng.randn(B, kvh, G, hd).astype(np.float32) * 0.5
    kp = rng.randn(N_LAYERS, n_blocks, kvh, bs, hd).astype(np.float32) * 0.5
    vp = rng.randn(N_LAYERS, n_blocks, kvh, bs, hd).astype(np.float32) * 0.5
    table = np.zeros((B, max_blocks), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))  # page 0 = NULL
    for b in range(B):
        if b in inactive:
            lens[b] = 1
            continue
        for j in range(-(-int(lens[b]) // bs)):
            table[b, j] = free.pop(0)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(lens))


def _quantize_pool(pages):
    """[L, N, kvh, bs, hd] f32 -> (int8 codes, [L, N, kvh] f32 scales),
    one scale per (layer, page, kv head) as the engine keeps them."""
    scales = jnp.maximum(jnp.abs(pages).max(axis=(-2, -1)) / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(pages / scales[..., None, None]),
                     -127, 127).astype(jnp.int8)
    return codes, scales


# The shapes the callers run and the edges of a block of P pages (P is
# 32 at a page of 16 tokens, 16 at one of 32: 512 tokens). Lengths: one
# token, exactly one page, exactly one block, one block plus a token,
# several blocks with a partial last one; the table is wider than the
# longest row needs and no multiple of P; the last row is an empty lane.
_EDGES16 = dict(bs=16, max_blocks=75, lens=(1, 16, 512, 513, 1061, 1),
                inactive=(5,))
_EDGES32 = dict(bs=32, max_blocks=35, lens=(1, 32, 512, 513, 1061, 1),
                inactive=(5,))
KERNEL_SHAPES = {
    "small": {},                                   # P = the table's 4
    "qwen2_kvh4_g7": dict(kvh=4, G=7, **_EDGES16),
    "llama3_kvh8_g4": dict(kvh=8, G=4, **_EDGES16),
    "tp4_shard_kvh1_g7": dict(kvh=1, G=7, **_EDGES16),
    "page32_kvh2_g4": dict(kvh=2, G=4, **_EDGES32),
    "inactive_first_row": dict(kvh=2, G=4, bs=16, max_blocks=40,
                               lens=(1, 600, 1, 40), inactive=(0, 2)),
}
KERNEL_CASES = [(kv, layer, "small") for kv in ("fp", "int8")
                for layer in range(N_LAYERS)]
KERNEL_CASES += [("fp", 1, s) for s in KERNEL_SHAPES if s != "small"]
KERNEL_CASES += [("int8", 2, "page32_kvh2_g4"),
                 ("int8", 0, "inactive_first_row")]


class TestPagedKernel:
    @pytest.mark.parametrize("kv,layer,shape", KERNEL_CASES)
    def test_interpret_matches_reference(self, kv, layer, shape):
        """The Pallas kernel (a row's pages in double-buffered blocks,
        one copy a page with all its kv heads + online softmax) must
        match its reference on ragged lengths at EVERY layer of the
        stacked pools — interpret mode executes the DMA and the scalar
        prefetch (table, lengths, layer) faithfully on CPU, and hands
        the kernel its buffers uninitialised (NaN), as the chip may. fp:
        the gather-then-masked-softmax reference; int8: the block-looped
        reference, which the kernel matches bit for bit. An empty lane
        (all-NULL table) comes out zeros and changes no neighbour."""
        from paddle_tpu.kernels.paged_attention import (
            _paged_attn_reference, _paged_attn_reference_int8,
            paged_attention_pallas)
        spec = KERNEL_SHAPES[shape]
        q, kp, vp, table, lens = _random_paged(**spec)
        dead = list(spec.get("inactive", ()))
        live = [b for b in range(len(lens)) if b not in dead]
        if kv == "int8":
            (kp, ks), (vp, vs) = _quantize_pool(kp), _quantize_pool(vp)
            out = paged_attention_pallas(q, kp, vp, table, lens, layer,
                                         interpret=True,
                                         kv_scales=(ks, vs))
            ref = _paged_attn_reference_int8(q, kp, vp, table, lens,
                                             layer, (ks, vs))
            np.testing.assert_array_equal(np.asarray(out)[live],
                                          np.asarray(ref)[live])
            assert not np.asarray(out)[dead].any()
            return
        # a traced layer, as the decode step's scan hands it over
        out = np.asarray(jax.jit(lambda l: paged_attention_pallas(
            q, kp, vp, table, lens, l, interpret=True))(jnp.int32(layer)))
        ref = np.asarray(_paged_attn_reference(q, kp, vp, table, lens,
                                               layer))
        assert np.allclose(out[live], ref[live], atol=2e-5), \
            np.abs(out[live] - ref[live]).max()
        assert not out[dead].any()
        # the layer's own pages were read, not a neighbour's
        other = np.asarray(_paged_attn_reference(
            q, kp, vp, table, lens, (layer + 1) % N_LAYERS))
        assert not np.allclose(out[live], other[live], atol=1e-2)

    # (kvh, bs, hd, pool dtype, table width) -> P
    @pytest.mark.parametrize("shape,pages", [
        ((4, 16, 128, "bfloat16", 160), 32),    # chat
        ((4, 16, 128, "bfloat16", 208), 32),    # doc_qa
        ((8, 16, 128, "bfloat16", 128), 32),    # llama3-8b, chip_smoke
        ((8, 32, 128, "int8", 64), 16),         # its int8 pools
        ((2, 16, 128, "bfloat16", 128), 32),    # a tp=4 shard of it
        ((1, 16, 128, "bfloat16", 160), 32),    # a tp=4 shard of qwen2
        ((2, 16, 128, "float32", 4), 4),        # a table narrower than P
        ((2, 8, 128, "float32", 96), 64),       # the CPU engines' pages
        ((32, 16, 128, "bfloat16", 512), 8),    # the budget binds
        ((64, 128, 256, "float32", 512), 1),    # never 0
    ])
    def test_pages_per_block_rule(self, shape, pages):
        """P comes from the operands' shapes alone: 512 tokens' worth of
        pages, fewer where the four page buffers (two slots each of K
        and V) would pass the VMEM budget the file states or the table
        is narrower, and never 0. (``tests/test_chip_compile.py`` holds
        every case it compiles to the same budget.)"""
        from paddle_tpu.kernels import paged_attention as pa
        kvh, bs, hd, dtype, mb = shape
        got = pa._pages_per_block(kvh, bs, hd, jnp.dtype(dtype), mb)
        assert got == pages
        page_bytes = kvh * bs * hd * jnp.dtype(dtype).itemsize
        assert got == 1 or 4 * got * page_bytes <= pa._PAGE_BUFFER_BYTES
        assert 1 <= got <= mb

    @pytest.mark.parametrize("kv", ["fp", "int8"])
    def test_token_write_touches_one_layer(self, kv):
        """The decode step's write of one token per row at layer l of
        the stacked pools lands at [l, page, :, off] and leaves every
        other layer's pages (and scales), and every other page of layer
        l, bit-identical."""
        from paddle_tpu.models.paged_stack import (_quantized_token_insert,
                                                   _token_insert)
        _, kp, _, table, lens = _random_paged(seed=5)
        bs = kp.shape[3]
        lens = np.asarray(lens) - 1          # write cursors inside pages
        page = np.asarray(table)[np.arange(len(lens)), lens // bs]
        off = lens % bs
        tok = jnp.asarray(np.random.RandomState(1).randn(
            len(lens), kp.shape[2], kp.shape[4]).astype(np.float32))
        for layer in range(N_LAYERS):
            if kv == "int8":
                pool, scales = _quantize_pool(kp)
                new, new_sc = jax.jit(_quantized_token_insert)(
                    pool, scales, jnp.int32(layer), page, off, tok)
                new_sc, scales = np.asarray(new_sc), np.asarray(scales)
                keep = np.ones(scales.shape[:2], bool)
                keep[layer, page] = False
                np.testing.assert_array_equal(new_sc[keep], scales[keep])
                assert (new_sc[layer, page] >= scales[layer, page]).all()
            else:
                pool = kp
                new = jax.jit(_token_insert)(pool, jnp.int32(layer), page,
                                             off, tok)
                np.testing.assert_array_equal(
                    np.asarray(new)[layer, page, :, off],
                    np.asarray(tok))
            new, pool = np.asarray(new), np.asarray(pool)
            keep = np.ones(pool.shape[:2], bool)
            keep[layer, page] = False
            np.testing.assert_array_equal(new[keep], pool[keep])
            assert (new[layer, page] != pool[layer, page]).any()
            if kv == "fp":
                # within the written pages only the token's row moved
                row = np.zeros(pool.shape[1:4] + (1,), bool)
                row[page, :, off] = True
                np.testing.assert_array_equal(
                    np.where(row, 0, new[layer]),
                    np.where(row, 0, pool[layer]))

    def test_reference_is_decode_attention_math(self):
        """The XLA fallback must be the EXACT math of
        llama._decode_attention over the gathered contiguous view —
        that identity is what makes paged-engine greedy outputs
        bit-match the contiguous engine on CPU."""
        from paddle_tpu.kernels.paged_attention import (
            _paged_attn_reference, gather_pages)
        from paddle_tpu.models.llama import _decode_attention
        q, kp, vp, table, lens = _random_paged(seed=3)
        out = _paged_attn_reference(q, kp, vp, table, lens, 1)
        ck = gather_pages(kp, table, 1)
        cv = gather_pages(vp, table, 1)
        np.testing.assert_array_equal(       # the layer where it lies
            np.asarray(ck), np.asarray(gather_pages(kp[1], table)))
        mask = jnp.arange(ck.shape[1])[None, :] < lens[:, None]
        ref = _decode_attention(q, ck, cv, mask)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_null_page_tail_is_ignored(self):
        """Scribbling on the NULL page (page 0) and on padded table
        entries must not change any row's output — that is the property
        that lets inactive rows and finished-mid-chunk rows write there
        with no masks in the compiled programs."""
        from paddle_tpu.kernels.paged_attention import \
            _paged_attn_reference
        q, kp, vp, table, lens = _random_paged(seed=7)
        ref = _paged_attn_reference(q, kp, vp, table, lens, 2)
        kp2 = kp.at[:, 0].set(1e3)
        vp2 = vp.at[:, 0].set(-1e3)
        out = _paged_attn_reference(q, kp2, vp2, table, lens, 2)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_entry_gate_uses_reference_off_tpu(self):
        from paddle_tpu.kernels.paged_attention import (
            _paged_attn_reference, paged_decode_attention)
        if jax.default_backend() == "tpu":
            pytest.skip("CPU-only gate check")
        q, kp, vp, table, lens = _random_paged(seed=11)
        out = paged_decode_attention(q, kp, vp, table, lens, 1)
        ref = _paged_attn_reference(q, kp, vp, table, lens, 1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _random_mixed(seed=0, B=4, T=8, kvh=2, G=4, hd=128, n_blocks=13,
                  bs=16, max_blocks=4, kv_lens=(37, 24, 64, 16),
                  q_lens=(1, 8, 1, 8)):
    """Random pool + tables for a MIXED launch: decode rows (q_len 1)
    beside prefill-chunk rows (q_len up to T) at ragged positions."""
    rng = np.random.RandomState(seed)
    q = rng.randn(B, T, kvh, G, hd).astype(np.float32) * 0.5
    kp = rng.randn(n_blocks, kvh, bs, hd).astype(np.float32) * 0.5
    vp = rng.randn(n_blocks, kvh, bs, hd).astype(np.float32) * 0.5
    kv_lens = np.asarray(kv_lens, np.int32)
    q_lens = np.asarray(q_lens, np.int32)
    table = np.zeros((B, max_blocks), np.int32)
    free = list(range(1, n_blocks))          # page 0 = NULL
    for b in range(B):
        for j in range(-(-int(kv_lens[b]) // bs)):
            table[b, j] = free.pop(0)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(kv_lens),
            jnp.asarray(q_lens))


def _mixed_oracle(q, kp, vp, table, kv_lens, q_lens):
    """Straight-line numpy math: query i of row b sits at position
    kv_len - q_len + i and attends positions <= its own. Padding query
    slots are left at zero (callers ignore them)."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    # pools are [N, kvh, bs, hd]; the oracle reads pages token-major
    kp, vp = np.swapaxes(kp, 1, 2), np.swapaxes(vp, 1, 2)
    table = np.asarray(table)
    B, T, kvh, G, hd = q.shape
    out = np.zeros((B, T, kvh, G, hd), np.float64)
    for b in range(B):
        n, qn = int(kv_lens[b]), int(q_lens[b])
        if n == 0:
            continue
        keys = np.concatenate([kp[p] for p in table[b]], 0)[:n]
        vals = np.concatenate([vp[p] for p in table[b]], 0)[:n]
        for i in range(qn):
            pos = n - qn + i
            for h in range(kvh):
                s = q[b, i, h] @ keys[:pos + 1, h].T / np.sqrt(hd)
                s -= s.max(axis=-1, keepdims=True)
                p = np.exp(s)
                p /= p.sum(axis=-1, keepdims=True)
                out[b, i, h] = p @ vals[:pos + 1, h]
    return out.astype(np.float32)


class TestMixedKernel:
    """ISSUE 7 tentpole layer 1: one launch serves decode rows and
    prefill-chunk rows at arbitrary position offsets. Every case runs
    the Pallas kernel in interpret mode AND the XLA reference against
    the straight-line numpy oracle."""

    def _check(self, q, kp, vp, table, kv_lens, q_lens):
        from paddle_tpu.kernels.paged_attention import (
            _mixed_attn_reference, mixed_attention_pallas)
        oracle = _mixed_oracle(q, kp, vp, table, kv_lens, q_lens)
        ref = np.asarray(_mixed_attn_reference(q, kp, vp, table,
                                               kv_lens, q_lens))
        out = np.asarray(mixed_attention_pallas(q, kp, vp, table,
                                                kv_lens, q_lens,
                                                interpret=True))
        ql = np.asarray(q_lens)
        for b in range(q.shape[0]):          # padding slots excluded
            sl = (b, slice(0, int(ql[b])))
            assert np.allclose(ref[sl], oracle[sl], atol=2e-5), \
                np.abs(ref[sl] - oracle[sl]).max()
            assert np.allclose(out[sl], oracle[sl], atol=2e-5), \
                np.abs(out[sl] - oracle[sl]).max()

    def test_decode_only_rows(self):
        """q_len=1 everywhere: the mixed launch IS the decode kernel
        (each query at position len-1)."""
        self._check(*_random_mixed(seed=21, T=1,
                                   kv_lens=(37, 5, 64, 16),
                                   q_lens=(1, 1, 1, 1)))

    def test_decode_only_matches_decode_reference(self):
        """A q_len=1 mixed launch must agree with the single-query
        decode reference on the same pool (same masked-softmax math,
        modulo the extra query dim's reduction order)."""
        from paddle_tpu.kernels.paged_attention import (
            _mixed_attn_reference, _paged_attn_reference)
        q, kp, vp, table, kv_lens, q_lens = _random_mixed(
            seed=23, T=1, kv_lens=(37, 5, 64, 16), q_lens=(1, 1, 1, 1))
        mixed = np.asarray(_mixed_attn_reference(
            q, kp, vp, table, kv_lens, q_lens))[:, 0]
        dec = np.asarray(_paged_attn_reference(
            q[:, 0], kp[None], vp[None], table, kv_lens, 0))
        assert np.allclose(mixed, dec, atol=2e-5)

    def test_chunk_only_rows(self):
        """Every row a prefill chunk mid-prompt: full q_len pages at
        position offsets, causal within the chunk."""
        self._check(*_random_mixed(seed=25, T=16,
                                   kv_lens=(48, 40, 16, 32),
                                   q_lens=(16, 16, 16, 16)))

    def test_interleaved_decode_and_chunks(self):
        """The serving shape: decode rows and chunk rows in ONE
        launch, ragged everything."""
        self._check(*_random_mixed(seed=27, T=8,
                                   kv_lens=(37, 24, 64, 16),
                                   q_lens=(1, 8, 1, 8)))

    def test_chunk_at_offset_zero_vs_mid_sequence(self):
        """A chunk whose queries START the sequence (kv_len == q_len:
        pure causal self-attention) beside one deep into resident
        history — the offset arithmetic must hold at both extremes."""
        self._check(*_random_mixed(seed=29, T=16,
                                   kv_lens=(16, 61, 64, 30),
                                   q_lens=(16, 16, 16, 14)))

    def test_final_partial_chunk(self):
        """The last chunk of a prompt is usually SHORTER than the
        window: q_len < T with padding query slots, and a kv_len that
        ends mid-page."""
        self._check(*_random_mixed(seed=31, T=16,
                                   kv_lens=(37, 21, 5, 50),
                                   q_lens=(5, 3, 5, 2)))

    def test_inactive_row_outputs_zeros(self):
        """kv_len=0 lanes (inactive slots in a fixed-shape launch)
        output exact zeros from BOTH the kernel and the reference —
        no NaNs leak from the empty softmax."""
        from paddle_tpu.kernels.paged_attention import (
            _mixed_attn_reference, mixed_attention_pallas)
        q, kp, vp, table, kv_lens, q_lens = _random_mixed(
            seed=33, T=8, kv_lens=(37, 0, 64, 0), q_lens=(1, 0, 8, 0))
        ref = np.asarray(_mixed_attn_reference(q, kp, vp, table,
                                               kv_lens, q_lens))
        out = np.asarray(mixed_attention_pallas(q, kp, vp, table,
                                                kv_lens, q_lens,
                                                interpret=True))
        assert np.all(np.isfinite(ref)) and np.all(np.isfinite(out))
        np.testing.assert_array_equal(ref[1], np.zeros_like(ref[1]))
        np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))

    def test_entry_gate_uses_reference_off_tpu(self):
        from paddle_tpu.kernels.paged_attention import (
            _mixed_attn_reference, mixed_paged_attention)
        if jax.default_backend() == "tpu":
            pytest.skip("CPU-only gate check")
        q, kp, vp, table, kv_lens, q_lens = _random_mixed(seed=35)
        out = mixed_paged_attention(q, kp, vp, table, kv_lens, q_lens)
        ref = _mixed_attn_reference(q, kp, vp, table, kv_lens, q_lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestBlockAllocator:
    def _alloc(self, n=9):
        from paddle_tpu.inference.paged_cache import BlockAllocator
        return BlockAllocator(n)

    def test_never_hands_out_null_page(self):
        a = self._alloc(9)
        pages = a.allocate(a.capacity)
        assert pages is not None and 0 not in pages
        assert sorted(pages) == list(range(1, 9))

    def test_all_or_nothing(self):
        a = self._alloc(9)
        assert a.allocate(9) is None          # > capacity: nothing taken
        assert a.num_free == 8
        first = a.allocate(6)
        assert a.allocate(3) is None          # only 2 left
        assert a.num_free == 2                # failed alloc took nothing
        a.free(first)
        assert a.num_free == 8

    def test_double_free_and_foreign_free_raise(self):
        a = self._alloc(5)
        pages = a.allocate(2)
        a.free(pages)
        with pytest.raises(ValueError):
            a.free(pages)                     # double free
        with pytest.raises(ValueError):
            a.free([0])                       # NULL page was never owned

    def test_fragmentation_interleaved_alloc_free(self):
        """Pages freed by interleaved retiring rows are reusable at once
        — a paged pool has no fragmentation failure mode (that is the
        point vs contiguous regions)."""
        a = self._alloc(17)                   # 16 usable
        rows = [a.allocate(4) for _ in range(4)]
        assert all(r is not None for r in rows)
        a.free(rows[0])
        a.free(rows[2])                       # free alternating rows
        again = a.allocate(8)                 # fits exactly in the holes
        assert again is not None
        assert sorted(again) == sorted(rows[0] + rows[2])
        assert a.num_free == 0
        assert a.stats() == {"capacity": 16, "used": 16, "free": 0,
                             "high_watermark": 16,
                             "total_allocated": 24, "total_freed": 8}

    def test_rejects_degenerate_pool(self):
        from paddle_tpu.inference.paged_cache import BlockAllocator
        with pytest.raises(ValueError):
            BlockAllocator(1)                 # only the NULL page


class TestPagedEngine:
    """The tentpole acceptance: paged DecodeEngine greedy outputs
    bit-match the contiguous engine AND solo generation, and sustained
    mixed arrivals never hit a reset."""

    def _workload(self, rng):
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (8, 10, 5, 6, 7, 5, 6, 4)]
        max_news = [16, 16, 4, 4, 4, 4, 4, 4]
        return prompts, max_news

    def test_paged_matches_contiguous_and_solo(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(1)
        prompts, max_news = self._workload(rng)
        solo = [solo_generate(m, p, mn)
            for p, mn in zip(prompts, max_news)]

        def run(**kw):
            eng = DecodeEngine(m, capacity=4, s_max=96, chunk=4, **kw)
            reqs = [_Request(p, mn)
                    for p, mn in zip(prompts, max_news)]
            pending = list(reqs)
            drive(eng, pending)
            return eng, [r.wait(timeout=1) for r in reqs]

        paged_eng, paged_out = run(paged=True, block_size=16)
        contig_eng, contig_out = run(paged=False)
        for po, co, so in zip(paged_out, contig_out, solo):
            np.testing.assert_array_equal(po, so)
            np.testing.assert_array_equal(po, co)
        assert paged_eng.resets == 1          # construction only

    @pytest.mark.parametrize("kv", ["fp", "int8"])
    def test_cold_prefill_either_side_of_a_block_edge(self, kv):
        """At ``s_max`` 64 the cold prefill runs 32 rows at a time:
        prompts of one block less a token, one block, one block and a
        token and a block and a part each equal solo ``generate``, and
        the engine counts the blocks it ran beside what whole windows
        would have been."""
        from paddle_tpu.models.paged_stack import prefill_block_rows
        m = shared_model()
        sizes = (31, 32, 33, 40, 9)
        prompts = make_prompts(np.random.RandomState(4), 128, sizes)
        assert prefill_block_rows(m.config, 64) == 32
        outs, eng = run_engine(m, prompts, kv_dtype=kv,
                               prefix_cache=False)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, solo_generate(m, p, 8))
        st = eng.stats()
        assert st["prefill_blocks"] == 1 + 1 + 2 + 2 + 1
        assert st["prefill_window_blocks"] == 2 * len(sizes)

    @pytest.mark.parametrize("s_max,rows,experts", [
        (2560, 256, None), (3328, 256, None), (2048, 256, None),
        (512, 256, None), (511, 128, None), (144, 64, None),
        (96, 32, None), (64, 32, None), (16, 8, None), (10, 8, None),
        # (experts a token, the router's, held here): a block of 256
        # rows brings a held expert 8 rows, 64 (every expert held:
        # Mixtral's 2 of 8), 16 and 15
        (33792, 512, (8, 256, 16)), (9216, 256, (2, 8, 8)),
        (9216, 256, (8, 128, 16)), (9216, 512, (15, 256, 16)),
        (9216, 256, (8, 256, 256)),
        (1024, 512, (8, 256, 16)), (1023, 256, (8, 256, 16)),
        (64, 32, (8, 256, 16))])
    def test_prefill_block_rows_rule(self, s_max, rows, experts):
        """256 rows a block, 512 where the configuration holds a share
        of a router's experts and 256 rows bring a held expert fewer
        than 16, wherever the window holds two of them, else the largest
        power of two that gives two blocks."""
        from types import SimpleNamespace
        from paddle_tpu.models.paged_stack import prefill_block_rows
        cfg = SimpleNamespace() if experts is None else SimpleNamespace(
            num_experts_per_tok=experts[0], n_routed_experts=experts[1],
            held_experts=(0, experts[2]))
        assert prefill_block_rows(cfg, s_max) == rows

    def test_sustained_admission_never_resets(self):
        """Continuous mixed arrivals far past the contiguous engine's
        global-fill horizon: the paged engine keeps admitting into freed
        pages and NEVER resets (the contiguous engine's failure mode
        this PR removes)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(2)
        eng = DecodeEngine(m, capacity=3, s_max=64, chunk=4,
                           block_size=8)
        solo = {}
        reqs, pending = [], []
        for i in range(12):                  # 12 staggered arrivals,
            n = int(rng.randint(3, 10))      # mixed lengths/max_new
            mn = int(rng.choice([3, 5, 9]))
            p = rng.randint(1, 128, (n,)).astype(np.int32)
            r = _Request(p, mn)
            solo[id(r)] = solo_generate(m, p, mn)
            reqs.append(r)
        # feed 2 per iteration: admission happens while earlier rows
        # are mid-generation, the continuous-batching shape
        queue = list(reqs)
        for _ in range(400):
            while queue and len(pending) < 2:
                pending.append(queue.pop(0))
            eng.admit(pending)
            eng.decode_once()
            if not queue and not pending and eng.idle():
                break
        else:
            raise AssertionError("engine did not drain")
        total_new = sum(r.max_new for r in reqs)
        assert total_new > eng.s_max         # past the global-fill horizon
        assert eng.resets == 1               # construction only — no reset
        for r in reqs:
            np.testing.assert_array_equal(r.wait(timeout=1),
                                          solo[id(r)])

    def test_admission_waits_for_pages_then_serves(self):
        """A pool too small for the whole wave: admission defers (no
        error) until retiring rows free pages; every request still
        serves with solo-parity tokens."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 128, (12,)).astype(np.int32)
                   for _ in range(4)]
        solo = [solo_generate(m, p, 4) for p in prompts]
        # 5 usable pages of 8 tokens: each row (prompt 12 + new 4 = 16)
        # needs exactly 2 pages at admission and never grows; 4 rows at
        # once would need 8 — admission must take turns on the pool
        eng = DecodeEngine(m, capacity=4, s_max=32, chunk=4,
                           block_size=8, n_blocks=6)
        reqs = [_Request(p, 4) for p in prompts]
        pending = list(reqs)
        drive(eng, pending)
        for r, s in zip(reqs, solo):
            np.testing.assert_array_equal(r.wait(timeout=1), s)
        assert eng.resets == 1

    def test_pool_exhaustion_fails_only_the_hungry_row(self):
        """When growth genuinely exhausts the pool, only a row that
        needed new pages fails; its freed pages let the others finish
        (ADVICE r5 #3 in paged form)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(4)
        p1 = rng.randint(1, 128, (7,)).astype(np.int32)
        p2 = rng.randint(1, 128, (5,)).astype(np.int32)
        solo2 = solo_generate(m, p2, 3)
        # 3 usable pages of 8: row 2 (5 + 3 = 8 tokens) lives entirely
        # in its one admission page; the 40-token row grows chunk by
        # chunk, absorbs the page row 2 frees at retire, and still
        # starves — it alone gets the exhaustion error
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4)
        r1, r2 = _Request(p1, 40), _Request(p2, 3)
        pending = [r1, r2]
        drive(eng, pending)
        with pytest.raises(RuntimeError, match="exhausted|s_max"):
            r1.wait(timeout=1)
        np.testing.assert_array_equal(r2.wait(timeout=1), solo2)
        assert eng._alloc.num_used == 0      # everything returned

    def test_row_hitting_s_max_fails_alone(self):
        """A row whose generation would outgrow s_max fails at the
        boundary; its neighbor is untouched (no engine-wide error, no
        reset)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(5)
        p1 = rng.randint(1, 128, (6,)).astype(np.int32)
        p2 = rng.randint(1, 128, (6,)).astype(np.int32)
        solo2 = solo_generate(m, p2, 5)
        eng = DecodeEngine(m, capacity=2, s_max=24, chunk=4,
                           block_size=8)
        r1, r2 = _Request(p1, 64), _Request(p2, 5)
        pending = [r1, r2]
        drive(eng, pending)
        with pytest.raises(RuntimeError, match="s_max"):
            r1.wait(timeout=1)
        np.testing.assert_array_equal(r2.wait(timeout=1), solo2)
        assert eng.resets == 1


def _parent_scatter(kp, vp, ks, vs, table_row, pad, offset=None,
                    kv_scales=None, seq_axis=None):
    """The oracle of :class:`TestPrefillPageWriter`: the writer the
    prefill programs had before they wrote whole pages, token by token
    along the in-page axis (window column j to ``[:, page, :, off]``,
    pad columns to the NULL page). fp pools, one device."""
    assert kv_scales is None and seq_axis is None
    bs = kp.shape[-2]
    j = jnp.arange(ks.shape[2])
    cpos = jnp.maximum(j - pad, 0) + (0 if offset is None else offset)
    page = jnp.where(j >= pad, jnp.take(table_row, cpos // bs), 0)
    off = jnp.where(j >= pad, cpos % bs, 0)
    return tuple(pool.at[:, page, :, off].set(
        jnp.swapaxes(toks[:, 0], 0, 1).astype(pool.dtype))
        for pool, toks in ((kp, ks), (vp, vs)))


@jax.jit
def _parent_scatter_int8(pool, scales, toks, table_row, pad, offset):
    """The same for ONE int8 pool: scatter-max of the page scales, the
    row's resident codes re-expressed in the grown scales, the new
    tokens quantized against them and written token by token. pool
    [L, N, kvh, bs, hd] int8; scales [L, N, kvh]; toks [L, sp, kvh, hd]
    float32."""
    bs = pool.shape[-2]
    j = jnp.arange(toks.shape[1])
    cpos = jnp.maximum(j - pad, 0) + offset
    page = jnp.where(j >= pad, jnp.take(table_row, cpos // bs), 0)
    off = jnp.where(j >= pad, cpos % bs, 0)
    amax = jnp.where((j >= pad)[None, :, None],
                     jnp.abs(toks).max(axis=-1), 0.0)
    new = scales.at[:, page].max(amax / 127.0)
    ratio = (scales[:, table_row] / new[:, table_row])[..., None, None]
    pool = pool.at[:, table_row].set(jnp.clip(jnp.round(
        pool[:, table_row].astype(jnp.float32) * ratio),
        -127, 127).astype(pool.dtype))
    qt = jnp.clip(jnp.round(toks / new[:, page][..., None]), -127, 127)
    return pool.at[:, page, :, off].set(
        jnp.swapaxes(qt, 0, 1).astype(pool.dtype)), new


# (cached prefix, tail tokens) of a row of s_max 64 on pages of 8: a
# cold row; a hit whose prefix ends (i) on a page boundary, (ii)
# mid-page, inside the row's copy-on-write page, (iii) such that the
# tail ends in the table's last page (the window of pages reaches past
# the table's end, into its NULL padding)
_WRITER_ROWS = {"cold": (0, 45), "hit_on_page_edge": (16, 11),
                "hit_mid_page": (13, 16), "hit_into_last_page": (43, 21)}


class TestPrefillPageWriter:
    """Both prefill programs write a row's keys and values as whole
    pages at ``[layer, page]`` of the donated pools. What lands in the
    pools is what the token-by-token scatter they had before put there
    (:func:`_parent_scatter`), and nothing else is touched."""

    S_MAX, BS, N_PAGES = 64, 8, 24
    SHARED, OTHERS = [3, 7], [5, 11, 19]

    def _table(self, cached, n):
        """Shared full prefix pages, then the row's own, then NULL."""
        own = [p for p in range(1, self.N_PAGES)
               if p not in self.SHARED + self.OTHERS][::-1]
        n_shared = cached // self.BS if cached else 0
        pages = self.SHARED[:n_shared] \
            + own[:-(-(cached + n) // self.BS) - n_shared]
        row = np.zeros((self.S_MAX // self.BS + 1,), np.int32)
        row[:len(pages)] = pages
        return row, pages

    def _run(self, monkeypatch, oracle, cached, n):
        """One admission through the engine's own compiled program, on
        pools filled with noise; returns (pools before, pools after)."""
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models import llama
        if oracle:
            monkeypatch.setattr(llama, "scatter_prefill_kv",
                                _parent_scatter)
        eng = DecodeEngine(shared_model("qwen2-debug"), capacity=2,
                           s_max=self.S_MAX, chunk=4, block_size=self.BS,
                           n_blocks=self.N_PAGES)
        rng = np.random.RandomState(7)
        before = tuple(rng.randn(*a.shape).astype(np.float32)
                       for a in eng._pool())
        seq = rng.randint(1, 128, (cached + n,)).astype(np.int32)
        row, _ = self._table(cached, n)
        st, embed, fnorm, lm = eng._weights()
        pool = tuple(jnp.asarray(a, eng._kp.dtype) for a in before)
        if cached == 0:
            ids = np.zeros((1, self.S_MAX), np.int32)
            ids[0, self.S_MAX - n:] = seq
            _, *after = eng._prefill(
                st, embed, fnorm, lm, eng._scales, jnp.asarray(ids),
                jnp.asarray([self.S_MAX - n], jnp.int32),
                jnp.asarray(row), *pool)
        else:
            sc = eng._bucket_window(n)
            ids = np.zeros((1, sc), np.int32)
            ids[0, sc - n:] = seq[cached:]
            _, *after = eng._prefix_prefill_for(sc)(
                st, embed, fnorm, lm, eng._scales, jnp.asarray(ids),
                jnp.asarray([sc - n], jnp.int32),
                jnp.asarray([cached], jnp.int32), jnp.asarray(row),
                *pool)
        return before, tuple(np.asarray(a, np.float32) for a in after)

    @pytest.mark.parametrize("row", list(_WRITER_ROWS))
    def test_programs_leave_what_the_token_scatter_left(self, row,
                                                        monkeypatch):
        cached, n = _WRITER_ROWS[row]
        before, new = self._run(monkeypatch, False, cached, n)
        _, old = self._run(monkeypatch, True, cached, n)
        table, pages = self._table(cached, n)
        if row == "hit_into_last_page":
            assert cached + n > (len(table) - 2) * self.BS
        untouched = [p for p in range(1, self.N_PAGES)
                     if p not in pages[cached // self.BS:]]
        assert set(self.SHARED[:cached // self.BS] + self.OTHERS) \
            <= set(untouched)
        for b, a, o in zip(before, new, old):
            # pages of other rows and shared prefix pages: bit for bit
            np.testing.assert_array_equal(a[:, untouched], b[:, untouched])
            if cached:
                # a tail is a read-modify-write: every page the table
                # names (every page but NULL, even) is the oracle's
                np.testing.assert_array_equal(a[:, 1:], o[:, 1:])
                continue
            # a cold row's pages are written whole and not read: every
            # position ``lens`` lets anyone read is the oracle's; what
            # lies past the prompt in its last page is masked
            a, o = (np.swapaxes(x[:, pages], 2, 3).reshape(
                x.shape[0], -1, *x.shape[2::2])[:, :n] for x in (a, o))
            np.testing.assert_array_equal(a, o)

    @pytest.mark.parametrize("row", list(_WRITER_ROWS))
    def test_int8_writer_keeps_the_token_scatters_numbers(self, row):
        """The int8 half: the same scales and the same codes as the
        token-by-token scatter, on every page but NULL (a cold row's
        pages are read-modify-written too there: scales only grow)."""
        from paddle_tpu.models.llama import scatter_prefill_kv
        cached, n = _WRITER_ROWS[row]
        sp = self.S_MAX if cached == 0 else 32
        rng = np.random.RandomState(3)
        shape = (2, self.N_PAGES, 2, self.BS, 16)
        pools = [rng.randint(-127, 128, shape).astype(np.int8)
                 for _ in range(2)]
        scales = [rng.uniform(0.001, 0.02, shape[:3]).astype(np.float32)
                  for _ in range(2)]
        toks = [rng.randn(2, 1, sp, 2, 16).astype(np.float32)
                for _ in range(2)]
        table, _ = self._table(cached, n)
        out = jax.jit(scatter_prefill_kv)(
            *map(jnp.asarray, pools + toks), jnp.asarray(table),
            jnp.int32(sp - n), jnp.int32(cached) if cached else None,
            kv_scales=tuple(map(jnp.asarray, scales)))
        for i in range(2):
            codes, grown = _parent_scatter_int8(
                pools[i], scales[i], toks[i][:, 0], jnp.asarray(table),
                sp - n, cached)
            np.testing.assert_array_equal(np.asarray(out[i])[:, 1:],
                                          np.asarray(codes)[:, 1:])
            np.testing.assert_array_equal(np.asarray(out[2 + i])[:, 1:],
                                          np.asarray(grown)[:, 1:])

    @pytest.mark.parametrize("options", [
        dict(), dict(chunked_prefill=True), dict(spec_decode=True),
        dict(chunked_prefill=True, spec_decode=True)],
        ids=lambda o: "+".join(o) or "prefix_cache_and_cow")
    def test_engine_emits_the_token_scatters_tokens(self, options,
                                                    monkeypatch):
        """Prefix cache, copy-on-write, chunked prefill and speculative
        decoding over the page writer: the tokens of an engine whose
        programs still scatter token by token."""
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models import llama
        m = shared_model("qwen2-debug")
        rng = np.random.RandomState(11)
        doc = rng.randint(1, 128, (21,)).astype(np.int32)
        prompts = [np.concatenate([doc[:k], t]) for k, t in zip(
            (21, 21, 16, 13, 0, 21),
            make_prompts(rng, 128, (4, 9, 11, 16, 30, 3)))]
        # repeats draft well: the verify windows accept and reject
        prompts.append(
            np.tile(rng.randint(1, 128, (5,)), 6).astype(np.int32))

        def run():
            eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                               block_size=8, **options)
            outs = []
            for p in prompts:       # one by one: each retires into the cache
                r = eng.submit(p, max_new_tokens=10)
                drive(eng)
                outs.append(np.asarray(r.wait(timeout=60)))
            return outs, eng.stats()

        new, stats = run()
        monkeypatch.setattr(llama, "scatter_prefill_kv", _parent_scatter)
        old, _ = run()
        for a, b in zip(new, old):
            np.testing.assert_array_equal(a, b)
        # hits that end on a page edge and mid-page (copy-on-write)
        assert stats["prefix_cache"]["hits"] >= 4
        assert stats["prefix_hit_tokens"] % 8
        if "chunked_prefill" in options:
            assert stats["prefill_chunks"] > len(prompts)
        if "spec_decode" in options:
            assert 0 < stats["spec"]["accepted"] < stats["spec"]["proposed"]


class TestContiguousClampedFinalChunk:
    """ADVICE r5 #3 (contiguous mode): at cache exhaustion, rows whose
    remaining max_new fits the leftover fill ride ONE clamped chunk out;
    only rows that genuinely cannot fit get the exhaustion error."""

    def test_near_finished_row_completes(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(6)
        pa = rng.randint(1, 128, (8,)).astype(np.int32)
        pb = rng.randint(1, 128, (8,)).astype(np.int32)
        solo_b = solo_generate(m, pb, 28)
        # fill walks 8 -> 32 in chunks of 8; the next chunk would cross
        # s_max=36, leaving space for 4: row B needs 3 more (fits the
        # clamp), row A needs 15 (cannot)
        eng = DecodeEngine(m, capacity=2, s_max=36, chunk=8,
                           paged=False)
        ra, rb = _Request(pa, 40), _Request(pb, 28)
        pending = [ra, rb]
        for _ in range(50):
            eng.admit(pending)
            eng.decode_once()
            if eng.idle() and not pending:
                break
        with pytest.raises(RuntimeError, match="exhausted"):
            ra.wait(timeout=1)
        np.testing.assert_array_equal(rb.wait(timeout=1), solo_b)
        assert eng.resets >= 2               # clamp drained, then reset

    def test_no_survivors_still_resets(self):
        """Every row too hungry for the leftover fill: all fail (the
        old behavior) and the engine resets for the next burst."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(7)
        pa = rng.randint(1, 128, (8,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=36, chunk=8,
                           paged=False)
        ra = _Request(pa, 60)
        pending = [ra]
        for _ in range(50):
            eng.admit(pending)
            eng.decode_once()
            if eng.idle() and not pending:
                break
        with pytest.raises(RuntimeError, match="exhausted"):
            ra.wait(timeout=1)
        assert eng.idle() and eng.resets >= 2

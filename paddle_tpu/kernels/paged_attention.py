"""Pallas ragged paged-attention decode kernel (TPU).

The serving decode path's KV cache becomes a BLOCK POOL
``[n_blocks, kvh, block_size, hd]`` a layer, the layers STACKED in one
array ``[L, n_blocks, kvh, block_size, hd]``, with a per-row block table
instead of one contiguous right-aligned region (reference shape: "Ragged Paged
Attention", arxiv 2604.15464 — the TPU-native kernel form of
vLLM/PagedAttention). Rows own ragged per-row lengths; the kernel
gathers each row's K/V blocks through the table, so admission never
needs a global fill position and the DecodeEngine never resets.

Design (single-query decode, one token per row):
- q: [B, kvh, G, hd] (grouped query heads for the token being decoded)
- k_pages/v_pages: [L, N, kvh, bs, hd], the engine's stacked pools as
  they lie in HBM, and ``layer``, the index of the layer to read — the
  kv head sits AHEAD of the ``[bs, hd]`` tile, so one (layer, page) is
  one contiguous, tile-aligned ``[kvh, bs, hd]`` with every kv head of
  its tokens. The decode step carries the pools through its layer scan
  and hands every layer the whole stack: a launch never sees a slice of
  a pool, so nothing of a pool's size is cut out, copied or re-laid for
  it, and a step costs the same whatever the pool holds. Page 0 is the
  reserved NULL page (allocators never hand it out; padded table entries
  and inactive rows write there, so fixed-shape programs need no masks)
- block_table: [B, max_blocks] int32 page ids (data argument — shapes
  stay fixed, so the two-compiled-programs serving discipline holds)
- seq_lens: [B] int32 valid tokens per row (ragged lengths)
- grid (B,): each program owns one ROW and walks its pages in blocks of
  P (:func:`_pages_per_block`: 512 tokens' worth, from the operands'
  shapes under a stated VMEM budget). A page is ONE ``make_async_copy``
  with all its kv heads, the page id scalar-prefetched from the table
  and the layer index beside it (``PrefetchScalarGridSpec``); the P
  copies of the next block are in flight, into the other of two VMEM
  slots ``[P, kvh, bs, hd]``, while the current block is folded. The
  last block of a row is partial: pages past the row's last are neither
  fetched nor waited for
- online softmax (f32 m/l/acc per kv head) with one step a BLOCK: one
  ``[G, hd] x [hd, P*bs]`` product, one mask (positions >= seq_len), one
  ``exp``, one ``[G, P*bs] x [P*bs, hd]`` product, one rescale
- a row that holds nothing (the engine's inactive lanes: a table that
  starts with the NULL page) writes zeros, starts no copy and waits for
  none — it costs a grid step
- every dot pins ``precision=DEFAULT`` like flash_attention.py: the
  process-wide ``jax_default_matmul_precision="high"`` (flags.py) is a
  precision the kernel lowering refuses
- interpret mode on CPU exactly like flash_attention.py: the DMA and
  scalar prefetch execute faithfully under ``interpret=True``, so CI
  proves the math without a TPU

The XLA fallback (`_paged_attn_reference`) gathers the row's pages
(indexed ``[layer, page]`` in the stacked pool, one gather) into
a contiguous view and runs the same masked softmax math as
``models.llama._decode_attention`` — bit-matching the contiguous-cache
decode on CPU, which is what the engine parity tests pin.

ISSUE 7 extends the file with a MIXED launch
(:func:`mixed_paged_attention`): one program serves decode rows (1
query at position len-1) and prefill-chunk rows (q_len queries at an
arbitrary position offset, causal within the chunk, attending to all
previously-written pages) side by side — the ragged-row shape chunked
prefill schedules into every decode step.

ISSUE 8 adds int8 PAGE READS: the block pool may store K/V as int8
with one f32 scale per (page, kv head) living beside the pool
(``kv_scales=(kscale, vscale)``, each [N, kvh] a layer, stacked
[L, N, kvh] for the decode entries), dequantized INSIDE
the attention program — the r6 weight-dequant-inside-the-kernel recipe
applied to the KV stream, halving the bytes a decode step moves.
The int8 XLA reference (:func:`_paged_attn_reference_int8`) is a
page-looped online softmax built from the SAME
:func:`_int8_block_update` helper the Pallas kernel calls page by page
inside each block it has fetched (every page has a scale of its own),
so the interpret-mode kernel and the reference execute the identical op
sequence on identical data and agree BIT-exactly — the parity
contract the int8 tests pin. A verify chunk (self-speculative
decoding's k-draft scoring step) is just a mixed-launch row whose
``q_len`` is the draft length + 1; :func:`verify_chunk_scores` is that
entry, spelled out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_attention_pallas",
           "mixed_paged_attention", "mixed_attention_pallas",
           "verify_chunk_scores", "gather_pages_dequant",
           "merge_softmax_partials", "seq_local_pages",
           "KV_SCALE_EPS", "NULL_PAGE"]

#: page id 0 is never allocated: padded block-table entries and
#: inactive rows read/write it, keeping every program shape-static.
NULL_PAGE = 0

#: floor for the per-(page, kv head) int8 scales: an unwritten page
#: dequantizes to exact zeros instead of dividing by zero, and the
#: running-max scale update's old/new ratio stays finite.
KV_SCALE_EPS = 1e-8

_NEG_INF = -1e30

#: what a trace calls the decode kernel: ``paged_decode_qk<width of a
#: key head>`` (``name=`` of the launch)
KERNEL_NAME = "paged_decode"


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

#: Tokens one softmax step covers where the shapes allow it: the kernel
#: walks a row in blocks of P = ``_BLOCK_TOKENS // bs`` pages. (On a v5e
#: at the serving cells' shapes 512 took 0.8 of the time of 256 and of
#: 128; a step's fixed work is what a longer block spreads.)
_BLOCK_TOKENS = 512

#: VMEM the page buffers of one launch may take: two slots (the block
#: being folded and the one in flight) each of K and V, every slot
#: ``[P, kvh, bs, hd]`` in the pool's dtype. A quarter of what the
#: compiler grants a kernel on a v5e (16 MiB), so q, the output and the
#: block's scores have room beside them.
_PAGE_BUFFER_BYTES = 4 * 2 ** 20


def _pages_per_block(kvh, bs, hd, dtype, max_blocks):
    """P, the pages one softmax step covers, from the operands' shapes
    alone: ``_BLOCK_TOKENS`` tokens' worth, fewer where four buffers of P
    pages would pass ``_PAGE_BUFFER_BYTES`` or the table is narrower, and
    never less than one."""
    page_bytes = kvh * bs * hd * jnp.dtype(dtype).itemsize
    return max(1, min(_BLOCK_TOKENS // bs,
                      _PAGE_BUFFER_BYTES // (4 * page_bytes), max_blocks))


def _stream_row(tables, lens, layer, k_hbm, v_hbm, o_ref, k_s, v_s, sem,
                fold, *, bs):
    """What the fp and int8 kernels share. One program = one ROW: its
    pages of layer ``layer[0]`` of the stacked pools stream HBM->VMEM in
    blocks of P pages, every page ONE copy ``[kvh, bs, hd]`` with all
    its kv heads, the next block in flight while ``fold(j, slot, h,
    n_pages, (m, l, acc))`` folds head ``h`` of block ``j`` into that
    head's online-softmax state. Pages past the row's last are neither
    fetched nor waited for. A row whose table starts with the NULL page
    holds nothing: it writes zeros and starts no copy."""
    b = pl.program_id(0)
    lyr = layer[0]
    ppb = k_s.shape[1]
    _, kvh, g, hd = o_ref.shape
    n_pages = jnp.minimum(jax.lax.div(lens[b] + bs - 1, bs),
                          tables.shape[1])
    n_blk = jax.lax.div(n_pages + ppb - 1, ppb)

    def copies(j, slot, op):
        """Start, or wait for, the copies of block ``j`` into ``slot``:
        one a page the block holds, the copies of a pool on one
        semaphore. (A loop and not P copies spelled out: that kernel took
        ten times as long to lower, in every process that serves.)"""
        def page(i, _):
            pg = tables[b, j * ppb + i]
            for x, (hbm, buf) in enumerate(((k_hbm, k_s), (v_hbm, v_s))):
                getattr(pltpu.make_async_copy(
                    hbm.at[lyr, pg], buf.at[slot, i], sem.at[x, slot]),
                    op)()
            return _

        jax.lax.fori_loop(0, jnp.minimum(ppb, n_pages - j * ppb), page, 0)

    live = tables[b, 0] != NULL_PAGE

    @pl.when(jnp.logical_not(live))
    def _nothing():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live)
    def _row():
        copies(0, 0, "start")

        def body(j, state):
            slot = jax.lax.rem(j, 2)

            copies(j + 1, 1 - slot, "start")   # none past the last block
            copies(j, slot, "wait")
            return tuple(fold(j, slot, h, n_pages, st)
                         for h, st in enumerate(state))

        state = jax.lax.fori_loop(0, n_blk, body, ((
            jnp.full((g,), _NEG_INF, jnp.float32),
            jnp.zeros((g,), jnp.float32),
            jnp.zeros((g, hd), jnp.float32)),) * kvh)
        for h, (_, l, acc) in enumerate(state):
            o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
                o_ref.dtype)


def _paged_kernel(tables, lens, layer, q_ref, k_hbm, v_hbm, o_ref, k_s,
                  v_s, sem, *, bs, scale):
    """fp pools: one ``[G, hd] x [hd, P*bs]`` product, one mask, one
    ``exp``, one ``[G, P*bs] x [P*bs, hd]`` product and one rescale per
    head and block (:func:`_stream_row`)."""
    b = pl.program_id(0)
    ppb = k_s.shape[1]
    g, hd = q_ref.shape[2:]
    hdv = v_s.shape[-1]         # V's heads may be narrower than K's
    t = ppb * bs
    n = lens[b]
    # q and K meet in the dtype they share (bfloat16 in an engine) and V
    # meets p in V's own: what precision=DEFAULT feeds the MXU anyway
    dt = jnp.promote_types(q_ref.dtype, k_s.dtype)
    pos = jax.lax.broadcasted_iota(jnp.int32, (g, t), 1)

    # The slots of a row's last block that hold no page keep what was
    # there before, and p = 0 times a NaN is a NaN: the launch's first
    # program (the grid runs in order) leaves both V buffers holding
    # zeros, and after it they hold zeros or pages of the pool.
    @pl.when(b == 0)
    def _clean():
        v_s[...] = jnp.zeros(v_s.shape, v_s.dtype)

    def fold(j, slot, h, n_pages, state):
        m, l, acc = state
        k = k_s[slot, :, h].reshape(t, hd)
        v = v_s[slot, :, h].reshape(t, hdv)
        s = jax.lax.dot_general(
            q_ref[0, h].astype(dt), k.astype(dt),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale   # [G, P*bs]
        # ragged tail: positions at or past the row's length are invalid
        # (a block that runs holds at least one that is not, so m_new is
        # finite and their exp is an exact zero)
        s = jnp.where(j * t + pos < n, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_new, l, acc

    _stream_row(tables, lens, layer, k_hbm, v_hbm, o_ref, k_s, v_s, sem,
                fold, bs=bs)


def _int8_block_update(q, kc, vc, ks, vs, m, l, acc, k_ids, n,
                       sm_scale):
    """ONE page of the int8 online softmax: dequantize the page's
    K/V codes with their per-(page, kv head) scales, fold the page into
    the running (m, l, acc) state. This helper is the WHOLE math of an
    int8 block — the Pallas kernel body and the XLA reference both call
    it, so interpret mode and the reference execute the identical op
    sequence and agree bit-exactly.

    q [G, hd] f32; kc/vc [bs, hd] int8 codes; ks/vs scalar f32 scales;
    k_ids [G, bs] absolute key positions; n scalar row length."""
    k = kc.astype(jnp.float32) * ks
    v = vc.astype(jnp.float32) * vs
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT) * sm_scale     # [G, bs]
    s = jnp.where(k_ids < n, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(k_ids < n, p, 0.0)
    alpha = jnp.exp(m - m_new)
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)
    return m_new, l, acc


def _out_struct(shape, *operands):
    """f32 output of a launch. Inside a ``shard_map`` region (the tp
    engine) the output varies over every mesh axis an operand varies
    over, and ``pallas_call`` has to be told so."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)


def _paged_kernel_int8(tables, lens, layer, q_ref, ks_ref, vs_ref, k_hbm,
                       v_hbm, o_ref, k_s, v_s, sem, *, bs, scale):
    """int8 twin of :func:`_paged_kernel`: the same copies
    (:func:`_stream_row`), but the pages are int8 codes dequantized
    inside the program, each with its own scale, so a fetched block is
    folded page by page through :func:`_int8_block_update` — the op
    sequence of the reference. ``ks_ref``/``vs_ref`` are this row's page
    scales in table order ([1, kvh, max_blocks] f32, an SMEM block) —
    gathered through the block table by the launch, so scalar memory
    holds one row's scales and never the pool's."""
    b = pl.program_id(0)
    ppb = k_s.shape[1]
    g = q_ref.shape[2]
    n = lens[b]
    pos = jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)

    def fold(j, slot, h, n_pages, state):
        q = q_ref[0, h].astype(jnp.float32)            # [G, hd]

        def page(i, state):
            pg = j * ppb + i
            return _int8_block_update(
                q, k_s[slot, i, h], v_s[slot, i, h], ks_ref[0, h, pg],
                vs_ref[0, h, pg], *state, pg * bs + pos, n, scale)

        return jax.lax.fori_loop(
            0, jnp.minimum(ppb, n_pages - j * ppb), page, state)

    _stream_row(tables, lens, layer, k_hbm, v_hbm, o_ref, k_s, v_s, sem,
                fold, bs=bs)


def paged_attention_pallas(q, k_pages, v_pages, block_table, seq_lens,
                           layer, interpret=False, kv_scales=None,
                           name=None):
    """Raw Pallas launch against layer ``layer`` of the STACKED pools.
    q [B, kvh, G, hd]; k_pages [L, N, kvh, bs, hd]; v_pages
    [L, N, kvh, bs, hdv], whose heads may have another width than K's
    (scores over ``hd``, the accumulator and the output ``hdv`` wide);
    block_table [B, max_blocks] int32; seq_lens [B] int32; layer an int32 scalar
    (data: the decode step's layer scan hands it its counter). The pools
    stay in HBM where they lie (``pl.ANY``) and the layer rides the
    scalar-prefetch lane beside the table, so a page is read at
    ``[layer, page]`` and nothing of a pool's size is sliced, moved
    or re-laid for the launch. One program a row, its pages in blocks
    of :func:`_pages_per_block`. Returns [B, kvh, G, hdv] f32; a row
    whose table starts with the NULL page comes out zeros.
    ``kv_scales=(kscale, vscale)`` ([L, N, kvh] f32 each) switches to the
    int8 kernel: the pools hold int8 codes, dequantized inside the
    program. ``name``: what a trace calls the launch (default
    ``paged_decode_qk<hd>``; a model that pads its key heads to a lane
    tile names the width it has)."""
    B, kvh, G, hd = q.shape
    bs = k_pages.shape[3]
    hdv = v_pages.shape[-1]
    block_table = jnp.asarray(block_table, jnp.int32)
    mb = block_table.shape[1]
    ppb = _pages_per_block(kvh, bs, max(hd, hdv), k_pages.dtype, mb)
    scale = 1.0 / (hd ** 0.5)
    q_spec = pl.BlockSpec((1, kvh, G, hd), lambda b, *_: (b, 0, 0, 0))
    o_spec = pl.BlockSpec((1, kvh, G, hdv), lambda b, *_: (b, 0, 0, 0))
    if kv_scales is None:
        kernel, scales, sc_specs = _paged_kernel, (), []
    else:
        # The scales a row needs are gathered through its block table
        # here and handed to its program as an SMEM block. (The whole
        # ``[N, kvh]`` arrays used to ride the scalar-prefetch lane; the
        # chip pads each row to 128 lanes there, so 4096 pages already
        # asked for 2 MiB of its 1 MiB of scalar memory.)
        kernel = _paged_kernel_int8
        scales = tuple(_row_page_scales(sc, block_table, layer)
                       for sc in kv_scales)
        sc_specs = [pl.BlockSpec(
            (1, kvh, mb), lambda b, *_: (b, 0, 0),
            memory_space=pltpu.SMEM)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, *sc_specs,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, kvh, bs, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb, kvh, bs, hdv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # [K/V, slot]
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, bs=bs, scale=scale),
        grid_spec=grid_spec,
        out_shape=_out_struct((B, kvh, G, hdv), q, k_pages, v_pages),
        interpret=interpret, name=name or f"{KERNEL_NAME}_qk{hd}",
    )(block_table, jnp.asarray(seq_lens, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *scales, k_pages,
      v_pages)


def _row_page_scales(scales, block_table, layer):
    """[L, N, kvh] page scales -> [B, kvh, max_blocks], row b's scales
    of layer ``layer`` in block-table order: what one row's program of
    the int8 kernel reads."""
    rows = jnp.asarray(scales, jnp.float32)[layer, block_table]
    return jnp.swapaxes(rows, 1, 2)                   # from [B, mb, kvh]


# ---------------------------------------------------------------------------
# XLA reference / fallback
# ---------------------------------------------------------------------------

def _rows_view(g, B, mb):
    """Gathered pages [B*mb, kvh, bs, hd] -> the contiguous per-row
    token view [B, mb*bs, kvh, hd] the reference math reads."""
    _, kvh, bs, hd = g.shape
    g = jnp.swapaxes(g.reshape(B, mb, kvh, bs, hd), 2, 3)
    return g.reshape(B, mb * bs, kvh, hd)


def _take_pages(pages, ids, layer):
    """Pages ``ids`` of a pool ``[N, ...]``, or with ``layer`` given of
    that layer of a stacked pool ``[L, N, ...]``: ONE gather either way,
    indexed ``[layer, page]`` where the pool lies, never a slice of it."""
    return jnp.take(pages, ids, axis=0) if layer is None \
        else pages[layer, ids]


def gather_pages(pages, block_table, layer=None):
    """[N, kvh, bs, hd] pool (or, with ``layer``, that layer of the
    stacked [L, N, kvh, bs, hd] pool) + [B, max_blocks] table ->
    contiguous per-row view [B, max_blocks*bs, kvh, hd] (padded tail
    reads the NULL page — masked out by seq_lens downstream)."""
    B, mb = block_table.shape
    g = _take_pages(pages, block_table.reshape(-1), layer)
    return _rows_view(g, B, mb)


def gather_pages_dequant(pages, block_table, scales, layer=None):
    """int8 counterpart of :func:`gather_pages`: gather code pages AND
    their per-(page, kv head) scales, dequantize to f32. pages
    [N, kvh, bs, hd] int8; scales [N, kvh] f32 (both with a leading L
    when ``layer`` is given). Returns
    [B, max_blocks*bs, kvh, hd] f32 (NULL-page tail dequantizes with
    whatever scale page 0 carries — masked out by seq_lens downstream
    exactly like the fp gather)."""
    B, mb = block_table.shape
    flat = block_table.reshape(-1)
    g = _take_pages(pages, flat, layer).astype(jnp.float32)
    sc = _take_pages(scales, flat, layer)          # [B*mb, kvh]
    return _rows_view(g * sc[:, :, None, None], B, mb)


def _paged_attn_reference(q, k_pages, v_pages, block_table, seq_lens,
                          layer):
    """Gather-then-masked-softmax over layer ``layer`` of the stacked
    pools, the exact math of
    models.llama._decode_attention's single-softmax branch — masked
    entries contribute exact zeros, so contiguous-cache decode and
    paged decode bit-match on the same tokens."""
    ck = gather_pages(k_pages, block_table, layer)  # [B, S, kvh, hd]
    cv = gather_pages(v_pages, block_table, layer)
    s_tot = ck.shape[1]
    mask = jnp.arange(s_tot)[None, :] < seq_lens[:, None]
    qf = q.astype(jnp.float32)                  # [B, kvh, G, hd]
    scale = q.shape[-1] ** 0.5
    s = jnp.einsum("bngd,btnd->bngt", qf,
                   ck.astype(jnp.float32)) / scale
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngt,btnd->bngd", p, cv.astype(jnp.float32))


def _paged_attn_reference_int8(q, k_pages, v_pages, block_table,
                               seq_lens, layer, kv_scales):
    """int8 XLA reference: a BLOCK-LOOPED online softmax, deliberately
    NOT the single-softmax gather shape of
    :func:`_paged_attn_reference`. Each (row, kv head) cell walks its
    page list through :func:`_int8_block_update` — the same helper the
    Pallas kernel body calls — so the interpret-mode kernel and this
    reference execute the identical op sequence on identical data and
    agree bit-exactly. B and kvh are static (shape-derived), so the
    python loops unroll at trace time; the per-cell page walk is a
    traced fori_loop over the row's ragged page count."""
    kscale = jnp.asarray(kv_scales[0], jnp.float32)
    vscale = jnp.asarray(kv_scales[1], jnp.float32)
    k_pages = jnp.asarray(k_pages)
    v_pages = jnp.asarray(v_pages)
    B, kvh, G, hd = q.shape
    bs = k_pages.shape[3]
    scale = 1.0 / (hd ** 0.5)
    tables = jnp.asarray(block_table, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    rows = []
    for b in range(B):
        n = lens[b]
        n_blk = jax.lax.div(n + bs - 1, bs)
        heads = []
        for h in range(kvh):
            qc = q[b, h].astype(jnp.float32)       # [G, hd]

            def body(j, carry, b=b, h=h, qc=qc, n=n):
                m, l, acc = carry
                page = tables[b, j]
                kc = k_pages[layer, page, h]
                vc = v_pages[layer, page, h]
                k_ids = j * bs + jax.lax.broadcasted_iota(
                    jnp.int32, (G, bs), 1)
                return _int8_block_update(
                    qc, kc, vc, kscale[layer, page, h],
                    vscale[layer, page, h],
                    m, l, acc, k_ids, n, scale)

            m0 = jnp.full((G,), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((G,), jnp.float32)
            acc0 = jnp.zeros((G, hd), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, n_blk, body,
                                          (m0, l0, acc0))
            heads.append(acc / jnp.maximum(l, 1e-30)[:, None])
        rows.append(jnp.stack(heads))
    return jnp.stack(rows)


def _kernel_serves(pages, v_pages=None):
    """The ONE place the paged entries choose between the Pallas kernels
    and the XLA references: the kernels on the TPU backend when a
    (page, kv head) slab ``[bs, hd]`` is tile-aligned for the pool's
    dtype, the references everywhere else (the CPU tests' oracle). The
    decode kernel takes float pools whose V heads have another width
    than K's (``v_pages``), each a whole number of lane tiles (the chip
    lays a head of 192 out 256 lanes wide in any case, and a page cut
    at 192 is refused: a model with such heads pads K itself)."""
    bs, hd = pages.shape[-2:]
    min_bs = 32 if pages.dtype == jnp.int8 else 8
    hdv = hd if v_pages is None else v_pages.shape[-1]
    if hdv != hd and (pages.dtype == jnp.int8 or hdv % 128):
        return False
    return (jax.default_backend() == "tpu" and hd % 128 == 0
            and bs % min_bs == 0)


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                           layer, kv_scales=None, seq_axis=None, n_seq=1,
                           name=None):
    """Entry used by the llama paged decode step, against layer
    ``layer`` (an int32 scalar, data) of the STACKED pools
    [L, N, kvh, bs, hd] (scales [L, N, kvh]): the Pallas kernel on
    TPU when the block pool is tileable (:func:`_kernel_serves`), else
    the XLA gather reference (CPU tests pin the reference's bit-parity
    with the contiguous path; the kernel's own parity is pinned in
    interpret mode). Every form reads the layer's pages where they lie:
    none takes a slice of a pool. ``kv_scales`` switches to the int8
    path.
    ``seq_axis`` (inside a shard_map whose pools are page-sharded over
    that mesh axis into ``n_seq`` stripes) switches to the
    partial-softmax form — each shard attends over its local pages and
    the partials merge with one collective (SURVEY §7.22)."""
    if seq_axis is not None and n_seq > 1:
        return _paged_decode_attention_seq(
            q, k_pages, v_pages, block_table, seq_lens, layer,
            seq_axis, n_seq, kv_scales=kv_scales)
    if _kernel_serves(k_pages, v_pages):
        return paged_attention_pallas(q, k_pages, v_pages, block_table,
                                      seq_lens, layer,
                                      kv_scales=kv_scales, name=name)
    if kv_scales is not None:
        return _paged_attn_reference_int8(
            q, k_pages, v_pages, block_table, seq_lens, layer,
            kv_scales)
    return _paged_attn_reference(q, k_pages, v_pages, block_table,
                                 seq_lens, layer)


# ---------------------------------------------------------------------------
# Mixed prefill-chunk + decode launch (ISSUE 7 tentpole)
# ---------------------------------------------------------------------------
# One launch serves rows of BOTH serving kinds ("Ragged Paged
# Attention"'s actual shape — decode is just the q_len=1 special case):
# - decode rows: 1 query token sitting at position kv_len-1
# - prefill-chunk rows: q_len query tokens ending at kv_len-1 (a page
#   of prompt scheduled into a decode step), causal WITHIN the chunk
#   and attending to every previously-written position through the
#   row's block table
# Contract: the chunk's own K/V are already resident in the pool
# (scatter-then-attend, the same convention as the decode step's
# lens+1), so query i of row b sits at absolute position
# ``kv_lens[b] - q_lens[b] + i`` and attends to positions <= its own.
# Query slots i >= q_lens[b] are padding: they compute finite garbage
# that callers ignore (no masks needed in the launch shape).

def _mixed_kernel(tables, kv_lens, q_lens, q_ref, k_hbm, v_hbm, o_ref,
                  k_s, v_s, ksem, vsem, *, bs, scale):
    """One program = one (row, kv_head): T*G query rows against the
    row's ragged page list with PER-QUERY causal limits; pages
    double-buffered HBM→VMEM exactly like the decode kernel."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    q = q_ref[0, :, 0].astype(jnp.float32)             # [T, G, hd]
    t, g, hd = q.shape
    q = q.reshape(t * g, hd)

    n = kv_lens[b]                                     # resident tokens
    qn = q_lens[b]                                     # valid queries
    n_blk = jax.lax.div(n + bs - 1, bs)
    # query row r = i*G + gg sits at position n - qn + i: its inclusive
    # attend limit. Padding queries (i >= qn) get limit >= n-1 — every
    # resident position, finite garbage out.
    qi = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (t * g, bs), 0), g)
    limit = n - qn + qi                                # [t*g, bs]

    def kdma(slot, j):
        return pltpu.make_async_copy(
            k_hbm.at[tables[b, j], h], k_s.at[slot], ksem.at[slot])

    def vdma(slot, j):
        return pltpu.make_async_copy(
            v_hbm.at[tables[b, j], h], v_s.at[slot], vsem.at[slot])

    m0 = jnp.full((t * g,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((t * g,), jnp.float32)
    acc0 = jnp.zeros((t * g, hd), jnp.float32)

    @pl.when(n_blk > 0)
    def _start():
        kdma(0, 0).start()
        vdma(0, 0).start()

    def body(j, carry):
        m, l, acc = carry
        slot = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < n_blk)
        def _prefetch():
            kdma(nxt, j + 1).start()
            vdma(nxt, j + 1).start()

        kdma(slot, j).wait()
        vdma(slot, j).wait()
        k = k_s[slot]                                  # [bs, hd]
        v = v_s[slot]
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale   # [t*g, bs]
        k_ids = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (t * g, bs), 1)
        ok = (k_ids <= limit) & (k_ids < n)            # ragged + causal
        s = jnp.where(ok, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, acc0))
    out = (acc / jnp.maximum(l, 1e-30)[:, None]).reshape(t, g, hd)
    o_ref[0, :, 0] = out.astype(o_ref.dtype)


def mixed_attention_pallas(q, k_pages, v_pages, block_table, kv_lens,
                           q_lens, interpret=False):
    """Raw Pallas launch for a MIXED batch. q [B, T, kvh, G, hd] (T =
    padded query tokens per row; decode rows use q_lens=1); k/v_pages
    [N, kvh, bs, hd]; block_table [B, max_blocks] int32; kv_lens [B]
    int32 resident tokens INCLUDING this launch's queries; q_lens [B]
    int32 valid query tokens. Returns [B, T, kvh, G, hd] f32."""
    B, T, kvh, G, hd = q.shape
    bs = k_pages.shape[2]
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_mixed_kernel, bs=bs, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, kvh),
        in_specs=[
            pl.BlockSpec((1, T, 1, G, hd),
                         lambda b, h, *_: (b, 0, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, T, 1, G, hd),
                               lambda b, h, *_: (b, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bs, hd), k_pages.dtype),
            pltpu.VMEM((2, bs, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_struct((B, T, kvh, G, hd), q, k_pages, v_pages),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32),
      jnp.asarray(q_lens, jnp.int32), q, k_pages, v_pages)


def _mixed_attn_reference(q, k_pages, v_pages, block_table, kv_lens,
                          q_lens, kv_scales=None):
    """Gather-then-masked-softmax over the per-query causal mask — the
    mixed counterpart of `_paged_attn_reference` (same exact-zeros
    masking, so a q_lens=1 launch is the decode math). Rows with no
    attendable position (kv_len 0) output exact zeros, matching the
    kernel's l=0 branch. ``kv_scales`` dequantizes int8 pools on
    gather."""
    if kv_scales is not None:
        ck = gather_pages_dequant(k_pages, block_table, kv_scales[0])
        cv = gather_pages_dequant(v_pages, block_table, kv_scales[1])
    else:
        ck = gather_pages(k_pages, block_table)  # [B, S, kvh, hd]
        cv = gather_pages(v_pages, block_table)
    T = q.shape[1]
    s_tot = ck.shape[1]
    pos = (kv_lens[:, None] - q_lens[:, None]
           + jnp.arange(T)[None, :])            # [B, T] query positions
    j = jnp.arange(s_tot)[None, None, :]
    ok = (j <= pos[:, :, None]) & (j < kv_lens[:, None, None])
    qf = q.astype(jnp.float32)                  # [B, T, kvh, G, hd]
    scale = q.shape[-1] ** 0.5
    s = jnp.einsum("btngd,bsnd->btngs", qf,
                   ck.astype(jnp.float32)) / scale
    s = jnp.where(ok[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("btngs,bsnd->btngd", p, cv.astype(jnp.float32))


def mixed_paged_attention(q, k_pages, v_pages, block_table, kv_lens,
                          q_lens, kv_scales=None, seq_axis=None,
                          n_seq=1):
    """Entry for mixed prefill-chunk + decode launches: the Pallas
    kernel on TPU when the pool is tileable, else the XLA gather
    reference (the kernel's parity is pinned in interpret mode; the
    serving engine's CPU chunk path rides the bucketed prefix-prefill
    programs, whose bit-parity the r7 tests pin). int8 pools
    (``kv_scales`` given) always take the gather reference — the mixed
    int8 kernel is the per-page fp8 follow-on's problem, and decode
    steps (the bandwidth-bound path ISSUE 8 targets) never come through
    here. ``seq_axis``/``n_seq`` switch to the page-sharded
    partial-softmax form exactly like :func:`paged_decode_attention`."""
    if seq_axis is not None and n_seq > 1:
        return _mixed_paged_attention_seq(
            q, k_pages, v_pages, block_table, kv_lens, q_lens,
            seq_axis, n_seq, kv_scales=kv_scales)
    if kv_scales is None and _kernel_serves(k_pages):
        return mixed_attention_pallas(q, k_pages, v_pages, block_table,
                                      kv_lens, q_lens)
    return _mixed_attn_reference(q, k_pages, v_pages, block_table,
                                 kv_lens, q_lens, kv_scales)


# ---------------------------------------------------------------------------
# Verify-chunk scoring (ISSUE 8 tentpole)
# ---------------------------------------------------------------------------

def verify_chunk_scores(q, k_pages, v_pages, block_table, kv_lens,
                        q_lens, kv_scales=None, seq_axis=None,
                        n_seq=1):
    """Attention for a speculative VERIFY chunk: row b's q_lens[b]
    query tokens are the pending next-input token plus its k drafts,
    already scattered into the pool at absolute positions
    ``kv_lens[b] - q_lens[b] .. kv_lens[b] - 1`` (scatter-then-attend,
    the decode-step convention). This is exactly the mixed launch
    contract — a verify chunk IS a prefill chunk whose tokens happen to
    be guesses — so the wrapper just documents the shape and delegates;
    query slots past q_lens[b] compute finite garbage the engine's
    accept loop never reads."""
    return mixed_paged_attention(q, k_pages, v_pages, block_table,
                                 kv_lens, q_lens, kv_scales=kv_scales,
                                 seq_axis=seq_axis, n_seq=n_seq)


# ---------------------------------------------------------------------------
# Sequence-parallel partials (2-D mesh, ISSUE 16 tentpole)
# ---------------------------------------------------------------------------
# Inside a shard_map over a (seq, tp) mesh the pools arrive PAGE-
# sharded: seq shard s holds global pages [s*n_local, (s+1)*n_local).
# The allocator stripes pages so the page at block-table column j is
# always in stripe j % n_seq (paged_cache.py), which makes the shard's
# attention a dense STRIDED gather — columns s, s+n_seq, ... of every
# table — rather than a masked full-width one. Each shard runs the
# masked online-softmax over only those local keys and emits partial
# (m, l, acc); ONE collective merge along seq (ring-attention math on a
# flat topology) finishes the softmax:
#     M = pmax(m);  w = exp(m - M)
#     out = psum(acc * w) / max(psum(l * w), eps)
# Masking uses the FINITE _NEG_INF, so a shard with zero valid keys
# contributes m = _NEG_INF, w = exp(_NEG_INF - M) -> 0 (or 1 when ALL
# shards are empty, where l = 0 makes the row exact zeros) — no NaNs,
# and q_len=0 padding rows keep the exact-zero contract.

def _seq_gather_ids(block_table, n_seq, n_local, bs, seq_axis):
    """This seq shard's strided view of every row's block table.
    Returns ``local`` [B, W] (page ids rebased into the shard's local
    pool, W = ceil(max_blocks / n_seq)) and ``k_ids`` [W*bs] (the
    ABSOLUTE key position of each gathered slot; slots from columns
    past the table width get a huge sentinel so every ``< len`` mask
    drops them)."""
    B, mb = block_table.shape
    shard = jax.lax.axis_index(seq_axis)
    W = -(-mb // n_seq)
    cols = shard + n_seq * jnp.arange(W, dtype=jnp.int32)   # [W]
    valid = cols < mb
    colsc = jnp.minimum(cols, mb - 1)
    pages = jnp.take(block_table, colsc, axis=1)            # [B, W]
    # clip, not mask: out-of-stripe ids only occur in NULL/pad entries,
    # whose keys the k_ids sentinel or seq_lens mask already kills.
    local = jnp.clip(pages - shard * n_local, 0, n_local - 1)
    k_ids = colsc[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)[None]
    k_ids = jnp.where(valid[:, None], k_ids, jnp.int32(2 ** 30))
    return local, k_ids.reshape(-1)


def seq_local_pages(page, n_local, seq_axis):
    """Rebase GLOBAL page ids for a WRITE on this seq shard: owned ids
    map into [0, n_local); non-owned ids map to n_local — a positive
    out-of-bounds index that ``.at[...].set(..., mode="drop")``
    discards (negative indices would WRAP, silently corrupting page
    n_local-1). Returns (local_ids, owned_mask)."""
    off0 = jax.lax.axis_index(seq_axis) * n_local
    owned = (page >= off0) & (page < off0 + n_local)
    return jnp.where(owned, page - off0, n_local), owned


def merge_softmax_partials(m, l, acc, axis):
    """Combine per-shard online-softmax partials along mesh ``axis``:
    m/l [...], acc [..., hd] -> merged [..., hd]. One pmax + two psums
    — the flat-topology form of the ring-attention accumulator
    combine."""
    M = jax.lax.pmax(m, axis)
    w = jnp.exp(m - M)
    L = jax.lax.psum(l * w, axis)
    ACC = jax.lax.psum(acc * w[..., None], axis)
    return ACC / jnp.maximum(L, 1e-30)[..., None]


def _paged_decode_attention_seq(q, k_pages, v_pages, block_table,
                                seq_lens, layer, seq_axis, n_seq,
                                kv_scales=None):
    """Page-sharded decode attention: the `_paged_attn_reference` math
    over this shard's strided columns, finished by
    :func:`merge_softmax_partials`. q [B, kvh_loc, G, hd]; pools
    [L, n_local, kvh_loc, bs, hd], read at layer ``layer``."""
    n_local, bs = k_pages.shape[1], k_pages.shape[3]
    local, k_ids = _seq_gather_ids(block_table, n_seq, n_local, bs,
                                   seq_axis)
    if kv_scales is not None:
        ck = gather_pages_dequant(k_pages, local, kv_scales[0], layer)
        cv = gather_pages_dequant(v_pages, local, kv_scales[1], layer)
    else:
        ck = gather_pages(k_pages, local, layer)  # [B, W*bs, kvh, hd]
        cv = gather_pages(v_pages, local, layer)
    mask = k_ids[None, :] < seq_lens[:, None]   # [B, W*bs]
    qf = q.astype(jnp.float32)
    scale = q.shape[-1] ** 0.5
    s = jnp.einsum("bngd,btnd->bngt", qf,
                   ck.astype(jnp.float32)) / scale
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    m = s.max(axis=-1)                          # [B, kvh, G]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bngt,btnd->bngd", p, cv.astype(jnp.float32))
    return merge_softmax_partials(m, l, acc, seq_axis)


def _mixed_paged_attention_seq(q, k_pages, v_pages, block_table,
                               kv_lens, q_lens, seq_axis, n_seq,
                               kv_scales=None):
    """Page-sharded mixed launch: `_mixed_attn_reference`'s per-query
    causal mask over this shard's strided columns + one partial merge.
    q [B, T, kvh_loc, G, hd]; rows with no attendable position on ANY
    shard (kv_len 0 / q_len 0 padding) come out exact zeros — every
    shard's l is 0 so the merged L floors at eps over a zero ACC."""
    n_local, bs = k_pages.shape[0], k_pages.shape[2]
    local, k_ids = _seq_gather_ids(block_table, n_seq, n_local, bs,
                                   seq_axis)
    if kv_scales is not None:
        ck = gather_pages_dequant(k_pages, local, kv_scales[0])
        cv = gather_pages_dequant(v_pages, local, kv_scales[1])
    else:
        ck = gather_pages(k_pages, local)       # [B, W*bs, kvh, hd]
        cv = gather_pages(v_pages, local)
    T = q.shape[1]
    pos = (kv_lens[:, None] - q_lens[:, None]
           + jnp.arange(T)[None, :])            # [B, T]
    j = k_ids[None, None, :]                    # absolute positions
    ok = (j <= pos[:, :, None]) & (j < kv_lens[:, None, None])
    qf = q.astype(jnp.float32)
    scale = q.shape[-1] ** 0.5
    s = jnp.einsum("btngd,bsnd->btngs", qf,
                   ck.astype(jnp.float32)) / scale
    okx = ok[:, :, None, None, :]
    s = jnp.where(okx, s, _NEG_INF)
    m = s.max(axis=-1)                          # [B, T, kvh, G]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(okx, p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("btngs,bsnd->btngd", p, cv.astype(jnp.float32))
    return merge_softmax_partials(m, l, acc, seq_axis)

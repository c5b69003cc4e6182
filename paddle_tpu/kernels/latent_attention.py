"""Attention over a LATENT cache (multi-head latent attention, absorbed
form), where a latent is key and value at once: the paged decode step's
kernel and the cold prefill's.

**Decode.** Every head of a row's one query token meets the row's cached
latents as they lie in their pages.

- q ``[B, H, lanes]``: a row's absorbed queries (``q_nope W_uk^T |
  q_rope``, zero past the latent's own width);
- pages ``[L, N, 1, bs, lanes]``: the engine's ONE stacked pool of
  latent pages (``[c_kv | k_r]`` padded to whole lane tiles, zero past
  it), ``layer`` the layer to read; page 0 is the NULL page;
- block_table ``[B, max_blocks]`` int32, kv_lens ``[B]`` int32: the
  tokens a row holds, its new one among them; 0 = no row;
- out ``[B, H, rank]`` float32: softmax(q . latent x ``scale``) over the
  row's own tokens times the latents' first ``rank`` values (``c_kv``);
  the caller takes it through ``W_uv``.

The kernel (:func:`latent_attention_pallas`, ``mla_latent_decode`` in a
trace) is ``kernels/paged_attention.py``'s decode kernel for this shape:
grid ``(B,)``, one program a ROW, its pages copied HBM -> VMEM in blocks
of P pages (``_BLOCK_TOKENS`` tokens), the next block in flight while
this one is folded; a page is read ONCE and used twice, as the ``[t, lanes]`` key
against all H heads and, its first ``rank`` lanes, as the value. The
online softmax's state (m, l ``[H, 1]``, acc ``[H, rank]`` float32)
lives in VMEM scratch. A row without tokens writes zeros, starts no copy
and costs a grid step; pages past a row's last are neither fetched nor
waited for, so a step's bytes are the live rows' own contexts. At 128
heads a cached token is ``2 x 128 x (576 + 512)`` operations for 1152
bytes: 242 a byte, the chip's ridge.

:func:`latent_attention_reference` is the same sum in plain ``jax.numpy``
a row at a time (the row's pages gathered to the table's length, a mask,
a dense softmax): the CPU path and the kernel's oracle.

**The cold prefill's causal pass.** One block of a right-aligned row's
queries against the row's latents from its first token to the block's
own, as they lie in the prefill's contiguous carry.

- qc ``[blk, H, lanes]``: the block's absorbed queries, at columns
  ``start..start + blk - 1`` of the window;
- lat_c ``[L, total, lanes]``: every layer's latents of the row so far,
  ``layer`` the layer to read; column ``pad`` holds position 0;
- allowed ``[blk, total]`` bool or None: what narrows a query's sight
  beside the causal rule (``glm_moe_dsa``'s chosen ``index_topk``);
- out ``[blk, H, rank]`` float32, as above over the keys at columns
  ``pad..`` the query's own; a query ahead of ``pad`` gives zeros.

The kernel (:func:`latent_prefill_pallas`, ``mla_latent_prefill`` in a
trace) is ONE launch a (block, layer): grid ``(H / G,)``, one program
the ``G x blk`` rows of G heads (``_prefill_tiles``: 4 x 512 at the
cells' blocks of 512 queries, 8 x 256 at a block of 256), their queries, the softmax's state (m, l ``[G, blk,
1]``) and the float32 accumulator (the output block itself, ``[G, blk,
rank]``) in VMEM for the whole walk over the keys; ``layer``, ``start``
and ``pad`` are prefetched scalars and the trip count is data. The keys
stay in HBM and come a tile of ``tk`` columns at a time
(``_PREFILL_KEYS``), the next tile's copy in flight while this one is
folded: scores, probabilities (cast to the latents' dtype for the second
product, as the plain pass casts them) and the rescaled sum never leave
VMEM, where the plain pass hands each of them to the next fusion through
HBM. Tiles no query of the block may see are neither fetched nor folded:
those wholly ahead of column ``pad`` and those behind the block's last
column; of the others only the ones that straddle ``pad`` or the
diagonal pay for the compare. Under ``allowed`` a ``[blk, tk]`` tile of
it comes with each tile of keys, one byte a pair, and every tile is
compared (the mask may hold columns a query cannot see: its ``topk``
largest of fewer seen). The plain pass
(``models/glm_moe_dsa.py::_causal_latent_pass``'s ``jnp`` body) is the
CPU path and the kernel's oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _out_struct

__all__ = ["latent_decode_attention", "latent_attention_pallas",
           "latent_attention_reference", "latent_prefill_pallas",
           "prefill_kernel_serves"]

KERNEL_NAME = "mla_latent_decode"
_NEG_INF = -1e30
# Tokens one softmax step covers: a row is walked in blocks of P =
# ``_BLOCK_TOKENS // bs`` pages. On a v5e at the code_ctx cell's shapes
# (10 rows of 10 k) 512 took 544 us a call, 1024 493 and, with one wait a
# full block, 455 and 2048 431 (PERF.md, Findings PR 44): a block's fixed
# work and the scalar loop over its pages' copies are what a longer block
# and a single wait spread. Two slots of 2048 x 640 bfloat16 are 5.2 MB of
# the 16 MiB a kernel is granted; the scores of a block are 1 MB.
_BLOCK_TOKENS = 2048


def _kernel(tables, lens, layer, q_ref, hbm, o_ref, buf, sem, m_s, l_s,
            acc_s, *, bs, rank, scale):
    b = pl.program_id(0)
    lyr = layer[0]
    ppb = buf.shape[1]
    t = ppb * bs
    n = lens[b]
    heads = q_ref.shape[1]
    n_pages = jnp.minimum(jax.lax.div(n + bs - 1, bs), tables.shape[1])
    n_blk = jax.lax.div(n_pages + ppb - 1, ppb)
    pos = jax.lax.broadcasted_iota(jnp.int32, (heads, t), 1)

    def page_copy(j, slot, i):
        return pltpu.make_async_copy(
            hbm.at[lyr, tables[b, j * ppb + i], 0], buf.at[slot, i],
            sem.at[slot])

    def each_page(held, fn):
        def page(i, carry):
            fn(i)
            return carry

        jax.lax.fori_loop(0, held, page, 0)

    def start(j, slot):
        """Start the copies of block ``j`` into ``slot``, one a page the
        block holds (none past the row's last page)."""
        each_page(jnp.minimum(ppb, n_pages - j * ppb),
                  lambda i: page_copy(j, slot, i).start())

    def wait(j, slot):
        """Wait for the copies of block ``j``: a full block's at once
        (the semaphore counts bytes, a slot's worth here), a row's last,
        partial block's page by page."""
        held = jnp.minimum(ppb, n_pages - j * ppb)

        @pl.when(held == ppb)
        def _all():
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot]).wait()

        each_page(jnp.where(held == ppb, 0, held),
                  lambda i: page_copy(j, slot, i).wait())

    # slots of a row's last block that hold no page keep what was there,
    # and p = 0 times a NaN is a NaN: the launch's first program leaves
    # the buffer zeros, after it it holds zeros or pages of the pool
    @pl.when(b == 0)
    def _clean():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    @pl.when(n == 0)
    def _nothing():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n > 0)
    def _row():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)
        start(0, 0)

        def body(j, _):
            slot = jax.lax.rem(j, 2)
            start(j + 1, 1 - slot)
            wait(j, slot)
            lat = buf[slot].reshape(t, buf.shape[-1])
            s = jax.lax.dot_general(
                q_ref[0], lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale    # [H, t]
            s = jnp.where(j * t + pos < n, s, _NEG_INF)
            m = m_s[...]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            m_s[...] = m_new
            l_s[...] = l_s[...] * alpha + p.sum(axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                p.astype(lat.dtype), lat[:, :rank],
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            return _

        jax.lax.fori_loop(0, n_blk, body, 0)
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(
            o_ref.dtype)


def latent_attention_pallas(q, pages, block_table, kv_lens, layer, *, rank,
                            scale, interpret=False):
    """The raw launch (see the module's text). Returns [B, H, rank]
    float32."""
    B, H, lanes = q.shape
    bs = pages.shape[3]
    block_table = jnp.asarray(block_table, jnp.int32)
    ppb = max(1, min(_BLOCK_TOKENS // bs, block_table.shape[1]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, lanes), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=_out_struct((B, H, rank), q, pages),
        interpret=interpret, name=KERNEL_NAME,
    )(block_table, jnp.asarray(kv_lens, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pages.dtype), pages)


def latent_attention_reference(q, pages, block_table, kv_lens, layer, *,
                               rank, scale):
    """Plain ``jax.numpy``, a row at a time: the row's pages of the
    layer gathered through its table to the table's length, scores
    masked past the row's tokens, a dense float32 softmax."""
    n_layers, n_pages, _, bs, lanes = pages.shape
    flat = pages.reshape(n_layers * n_pages, bs, lanes)

    def row(xs):
        qr, table, n = xs
        lat = flat.at[layer * n_pages + table].get(
            mode="promise_in_bounds").reshape(-1, lanes)
        s = jnp.einsum("hc,uc->hu", qr.astype(lat.dtype), lat,
                       preferred_element_type=jnp.float32) * scale
        ok = (jnp.arange(lat.shape[0]) < n)[None, :]
        s = jnp.where(ok, s, _NEG_INF)
        p = jnp.where(ok, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
        p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("hu,ur->hr", p.astype(lat.dtype), lat[:, :rank],
                          preferred_element_type=jnp.float32)

    return jax.lax.map(row, (q, jnp.asarray(block_table, jnp.int32),
                             jnp.asarray(kv_lens, jnp.int32)))


def kernel_serves(pages):
    """The kernel on the TPU backend over pages of whole tiles; the
    reference elsewhere (the CPU tests' path)."""
    bs, lanes = pages.shape[-2:]
    return (jax.default_backend() == "tpu" and lanes % 128 == 0
            and bs % 8 == 0)


def latent_decode_attention(q, pages, block_table, kv_lens, layer, *, rank,
                            scale):
    """What a latent family's decode step calls."""
    fn = latent_attention_pallas if kernel_serves(pages) \
        else latent_attention_reference
    return fn(q, pages, block_table, kv_lens, layer, rank=rank, scale=scale)


# -- the cold prefill's causal pass ------------------------------------------

PREFILL_KERNEL_NAME = "mla_latent_prefill"
# Rows (heads x the block's queries) one program keeps its softmax state
# and its accumulator for, and keys one fold takes; PERF.md, Findings PR
# 45, has what other sizes read on a v5e.
_PREFILL_ROWS = 2048
_PREFILL_KEYS = 512
_PREFILL_VMEM_BYTES = 64 << 20
# what a score no query may see is set to: far under the state's first
# maximum (``_NEG_INF``), so that ``exp(s - m)`` of a row that has seen
# nothing yet is 0 and not ``exp(0)``
_UNSEEN = 2 * _NEG_INF


def _prefill_tiles(heads, blk, total):
    """(heads a program, keys a fold) for a block of ``blk`` queries over
    a window of ``total`` columns: the most heads that divide ``heads``
    within ``_PREFILL_ROWS`` rows; whole blocks of keys, as many as
    divide the window within ``_PREFILL_KEYS``."""
    def most(n, at_most):
        return max(d for d in range(1, n + 1)
                   if n % d == 0 and (d == 1 or d * blk <= at_most))

    return most(heads, _PREFILL_ROWS), blk * most(total // blk, _PREFILL_KEYS)


def _prefill_kernel(at, q_ref, hbm, *refs, rank, scale, tk, masked):
    if masked:
        ok_hbm, o_ref, buf, sem, ok_buf, ok_sem, m_s, l_s = refs
    else:
        o_ref, buf, sem, m_s, l_s = refs
    heads, blk, lanes = q_ref.shape
    lyr, start, pad = at[0], at[1], at[2]
    # the tiles some query of the block may see: from the one that holds
    # column ``pad`` to the one that holds the block's last column
    lo = jax.lax.div(pad, tk)
    hi = jax.lax.div(start + blk - 1, tk) + 1

    def copies(j, slot):
        col = pl.multiple_of(j * tk, tk)
        out = [pltpu.make_async_copy(hbm.at[lyr, pl.ds(col, tk)],
                                     buf.at[slot], sem.at[slot])]
        if masked:
            out.append(pltpu.make_async_copy(ok_hbm.at[:, pl.ds(col, tk)],
                                             ok_buf.at[slot],
                                             ok_sem.at[slot]))
        return out

    def seen(j):
        """[blk, tk]: the tile's keys each query of the block may see."""
        kcol = j * tk + jax.lax.broadcasted_iota(jnp.int32, (blk, tk), 1)
        qcol = start + jax.lax.broadcasted_iota(jnp.int32, (blk, tk), 0)
        return (kcol <= qcol) & (kcol >= pad)

    def fold(slot, ok):
        """One tile of keys into every head's state; ``ok`` [blk, tk] or
        None where every query sees every key of the tile."""
        keys = buf[slot]
        s = jax.lax.dot_general(
            q_ref[...].reshape(heads * blk, lanes), keys,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        s = s.reshape(heads, blk, tk)
        if ok is not None:
            s = jnp.where(ok[None], s, _UNSEEN)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(keys.dtype).reshape(heads * blk, tk),
                     keys[:, :rank], preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.DEFAULT)
        o_ref[...] = o_ref[...] * alpha + pv.reshape(heads, blk, rank)

    m_s[...] = jnp.full(m_s.shape, _NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    for c in copies(lo, 0):
        c.start()

    def body(j, _):
        slot = jax.lax.rem(j - lo, 2)

        @pl.when(j + 1 < hi)
        def _next():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        if masked:
            fold(slot, seen(j) & (ok_buf[slot] != 0))
            return _
        # only a tile that straddles ``pad`` or the diagonal pays for
        # the compare
        edge = (j * tk < pad) | ((j + 1) * tk - 1 > start)
        pl.when(edge)(lambda: fold(slot, seen(j)))
        pl.when(jnp.logical_not(edge))(lambda: fold(slot, None))
        return _

    jax.lax.fori_loop(lo, hi, body, 0)
    o_ref[...] = o_ref[...] / jnp.maximum(l_s[...], 1e-30)


def latent_prefill_pallas(qc, lat_c, layer, start, pad, allowed=None, *,
                          rank, scale, interpret=False):
    """The raw launch of the cold prefill's causal pass (see the
    module's text). Returns [blk, H, rank] float32."""
    blk, H, lanes = qc.shape
    total = lat_c.shape[1]
    group, tk = _prefill_tiles(H, blk, total)
    masked = allowed is not None
    whole = lambda g, *_: (g, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // group,),
        in_specs=[pl.BlockSpec((group, blk, lanes), whole),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * masked,
        out_specs=pl.BlockSpec((group, blk, rank), whole),
        scratch_shapes=[pltpu.VMEM((2, tk, lanes), lat_c.dtype),
                        pltpu.SemaphoreType.DMA((2,))]
        + [pltpu.VMEM((2, blk, tk), jnp.int8),
           pltpu.SemaphoreType.DMA((2,))] * masked
        + [pltpu.VMEM((group, blk, 1), jnp.float32),
           pltpu.VMEM((group, blk, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, rank=rank, scale=scale, tk=tk,
                          masked=masked),
        grid_spec=grid_spec,
        out_shape=_out_struct((H, blk, rank), qc, lat_c),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret, name=PREFILL_KERNEL_NAME,
    )(jnp.asarray((layer, start, pad), jnp.int32),
      jnp.swapaxes(qc, 0, 1).astype(lat_c.dtype), lat_c,
      *([allowed.astype(jnp.int8)] if masked else []))
    return jnp.swapaxes(out, 0, 1)


def prefill_kernel_serves(qc, lat_c, rank):
    """The prefill kernel on the TPU backend over whole tiles of queries
    and lanes; the caller's plain pass elsewhere (the CPU tests' path)."""
    return (jax.default_backend() == "tpu" and qc.shape[0] % 128 == 0
            and lat_c.shape[-1] % 128 == 0 and rank % 128 == 0)

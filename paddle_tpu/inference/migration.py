"""Cross-worker KV page transplant (ISSUE 14 tentpole; reference
shape: disaggregated prefill/decode serving — DistServe/Splitwise-style
role splits — built on the "Ragged Paged Attention" stance that a KV
BLOCK is the transferable unit of state, PAPERS.md arXiv 2604.15464).

The r9 `GlobalPrefixDirectory` shares the fleet's prefix index but
pages never moved: a request whose best prefix lived on a saturated
worker re-prefilled cold elsewhere. This module moves the pages. One
primitive, :func:`transplant_prefix`, copies a published radix chain
from one engine's block pool into another's:

1. PIN — the OWNER's ``PrefixCache.match`` takes the source-side
   references (the same call admission trusts — the directory stays a
   hint). Matched pages are pinned at refcount >= 2, so a racing LRU
   eviction can never free them mid-copy: ``evict`` only frees
   refcount-1 nodes. A chain already evicted simply fails the match —
   the caller counts a stale hint and cold-prefills. One cold prefill,
   never a wrong answer.
2. ALLOCATE — ``dst._alloc.allocate(k)`` (falling back to the
   destination's own LRU eviction once); all-or-nothing, so a full
   destination aborts before anything moves.
3. COPY — every pool array (fp 2-tuple or int8 codes+scales 4-tuple)
   rides ONE batched gather/scatter launch when the two pools share a
   device placement. The page axis is UNSHARDED in ``pool_specs``, so
   the same program is spec-preserving on tp-sharded pools. Pools on
   disjoint placements (fleet workers own disjoint tp submeshes)
   bounce through host memory instead — the in-process stand-in for
   the multi-host ICI/RDMA hop (ROADMAP). int8 destinations drain
   their scale-reset list BEFORE the copy so the transplanted
   running-max scales land after the eps reset, not under it.
4. RE-LINK — ``dst._cache.insert(chain, new_pages)`` publishes the
   chain in the destination's radix tree (first-wins: segments the
   destination already caches keep their incumbent page and the
   transplanted duplicate frees on the decref below), then the
   transplant drops its own allocate() references and releases the
   source match pins.

Only allocate/incref/decref touch either allocator, so the ISSUE 3
conservation invariant (``total_allocated - total_freed == in_use``)
holds on BOTH pools by construction — asserted in the transplant tests
and exposed as ``BlockAllocator.conservation_ok``.

Index buckets: launch shapes are keyed on :func:`_bucket_pages`
(powers of two), with the pad lanes pointing at the NULL page — a
scratch page on both pools by design — so transplants of different
sizes share a few compiled programs instead of recompiling per chain
length (SC06 discipline).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

from ..utils.log import get_logger, log_kv

__all__ = ["MigrationResult", "transplant_prefix"]

_log = get_logger("paddle_tpu.inference.migration")


@dataclass
class MigrationResult:
    """One transplant's outcome. ``reason`` is ``"ok"`` when pages
    moved; otherwise why nothing did: ``"no_chain"`` (under one full
    block, or a zero budget), ``"stale"`` (the owner's match refuted
    the caller's hint — the directory-staleness signal), or
    ``"dst_full"`` (destination pool could not fund the chain).
    ``fused`` records whether the copy was the single-launch
    gather/scatter or the cross-placement host bounce."""

    pages_src: list = field(default_factory=list)
    pages_dst: list = field(default_factory=list)
    tokens: int = 0
    reason: str = "ok"
    fused: bool = False

    @property
    def pages(self) -> int:
        return len(self.pages_dst)

    @property
    def moved(self) -> bool:
        return bool(self.pages_dst)


def _bucket_pages(n: int) -> int:
    """Launch-shape bucket for transplant index vectors: powers of two
    from 4. Chains of mixed length share a handful of compiled copy
    programs; pad lanes target the NULL page on both pools."""
    b = 4
    while b < n:
        b *= 2
    return b


def _fused_copy(src_idx, dst_idx, src_pool, dst_pool):
    """ONE batched gather/scatter over every pool array (codes AND the
    int8 page scales). The source pool is a LIVE operand — it keeps
    serving the source engine, so it is never donated; only the
    destination pool donates and rebinds (SC09 discipline)."""
    return tuple(d.at[:, dst_idx].set(s[:, src_idx])
                 for s, d in zip(src_pool, dst_pool))


def _transplant_prog_for(dst):
    """The destination engine's cached fused-copy program, built on
    first transplant. Launch shapes are bucketed before this is called,
    so jit's shape cache holds one program per bucket. Only argument 3
    (the destination pool) donates; the source pool is a live operand
    serving its own engine and is never donated (SC09)."""
    import jax
    prog = dst._transplant_prog
    if prog is None:
        prog = jax.jit(_fused_copy, donate_argnums=(3,))
        if dst.compiles is not None:
            prog = dst.compiles.wrap("kv_transplant", prog)
        dst._transplant_prog = prog
    return prog


def _check_compatible(src, dst) -> None:
    """Transplants require byte-compatible pool layouts — same model
    geometry, block size and kv dtype. Pool DEPTH (n_blocks) may
    differ; page ids are remapped through the allocators anyway."""
    if not (src.paged and dst.paged):
        raise ValueError("transplant requires paged engines on both "
                         "ends")
    if src._state_specs or dst._state_specs:
        # pages are all a transplant moves; a recurrent state as it
        # stood at the prefix's end would have to travel with them
        raise ValueError("transplant cannot carry per-slot recurrent "
                         "state: a model with PagedPrograms.slot_state "
                         "keeps its prefix on one engine")
    if src.block_size != dst.block_size:
        raise ValueError(
            f"block_size mismatch: src={src.block_size} "
            f"dst={dst.block_size}")
    if src.kv_dtype != dst.kv_dtype:
        raise ValueError(
            f"kv_dtype mismatch: src={src.kv_dtype!r} "
            f"dst={dst.kv_dtype!r}")
    ss, ds = src._kp.shape, dst._kp.shape
    if ss[0] != ds[0] or ss[2:] != ds[2:] or \
            src._kp.dtype != dst._kp.dtype:
        raise ValueError(
            f"pool layout mismatch: src {ss}/{src._kp.dtype} vs "
            f"dst {ds}/{dst._kp.dtype}")


def _copy_blocks(src, dst, src_pages, dst_pages) -> bool:
    """Device copy of ``src_pages`` -> ``dst_pages`` across pools.
    Returns True for the fused single-launch path, False for the
    cross-placement host bounce."""
    import jax
    import numpy as _np
    from .sharding import same_pool_placement
    kb = _bucket_pages(len(src_pages))
    si = _np.zeros((kb,), _np.int32)
    si[:len(src_pages)] = src_pages
    di = _np.zeros((kb,), _np.int32)
    di[:len(dst_pages)] = dst_pages
    src_pool = src._pool()
    dst_pool = dst._pool()
    if same_pool_placement(src.mesh, dst.mesh):
        import jax.numpy as jnp
        prog = _transplant_prog_for(dst)
        dst_pool = prog(jnp.asarray(si), jnp.asarray(di), src_pool,
                        dst_pool)
        dst._set_pool(dst_pool)
        dst._c_device_calls.inc()
        return True
    # disjoint placements (fleet workers on disjoint tp submeshes):
    # gather on the source mesh, bounce through host, scatter on the
    # destination mesh — the in-process stand-in for the multi-host
    # ICI/RDMA hop. One gather + one scatter per pool array.
    out = []
    for s, d in zip(src_pool, dst_pool):
        vals = _np.asarray(s[:, si])
        out.append(d.at[:, di].set(vals))
    dst._set_pool(tuple(out))
    dst._c_device_calls.inc(len(out))
    return False


def transplant_prefix(src, dst, tokens, max_pages=None
                      ) -> MigrationResult:
    """Move the longest cached full-block prefix of ``tokens`` from
    engine ``src``'s pool into engine ``dst``'s pool and radix cache.

    ``max_pages`` bounds the chain (None = whole match). Returns a
    :class:`MigrationResult`; on any non-``"ok"`` reason NOTHING has
    changed on either allocator. Raises only on layout-incompatible
    engines (a config bug, not a runtime race)."""
    import numpy as _np
    res = MigrationResult()
    if src is dst:
        res.reason = "no_chain"
        return res
    _check_compatible(src, dst)
    if src._cache is None or dst._cache is None:
        res.reason = "no_chain"
        return res
    seq = _np.asarray(tokens).reshape(-1)
    bs = src.block_size
    budget = int(max_pages) if max_pages is not None \
        else seq.size // bs
    if budget <= 0 or seq.size < bs:
        res.reason = "no_chain"
        return res
    # PIN: the owner's match is the authority (directory hints may be
    # stale). Full pages only — a partial leaf COWs on the destination
    # at admission, exactly as it would on the source.
    m = src._cache.match(seq, min(seq.size, budget * bs))
    src._cache.release_cow(m)
    src_pages = list(m.pages)
    k = len(src_pages)
    if k == 0:
        src._cache.release(m)
        res.reason = "stale"
        return res
    new_pages = dst._alloc.allocate(k)
    if new_pages is None:
        # lean on the destination's own LRU once before giving up —
        # never preempt running rows for an optimization
        dst._evict_cached(k - dst._alloc.num_free)
        new_pages = dst._alloc.allocate(k)
    if new_pages is None:
        src._cache.release(m)
        res.reason = "dst_full"
        return res
    # int8: the fresh pages sit on dst's scale-reset list; drain NOW so
    # the copied running-max scales land AFTER the eps reset (the same
    # before-COW ordering the chunked-prefill path uses)
    dst._drain_scale_resets()
    res.fused = _copy_blocks(src, dst, src_pages, new_pages)
    chain = seq[:k * bs]
    dst._cache.insert(chain, new_pages)
    for p in new_pages:
        # drop the allocate() reference: adopted pages now belong to
        # dst's tree; a first-wins duplicate frees right here
        dst._alloc.decref(p)
    src._cache.release(m)
    res.pages_src = src_pages
    res.pages_dst = new_pages
    res.tokens = k * bs
    log_kv(_log, "kv_transplant", level=logging.DEBUG,
           src=src.worker_id, dst=dst.worker_id, pages=k,
           tokens=res.tokens, fused=res.fused)
    return res

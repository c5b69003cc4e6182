"""What the paged programs of every model family share. A family module
supplies its equations (one block's layers, one decode step), what its
carry holds and what it writes at the window's end; ``DecodeEngine`` meets
it through :class:`PagedPrograms`; both import this module and neither
the other's internals. Here: the seam (:class:`PagedPrograms`,
:func:`prefill_block_rows`, how a program writes the pool), the cold
prefill's walk over a row in blocks (:func:`walk_blocks`), the scan over
a stack's runs of like layers (:func:`scan_runs`), the greedy decode chunk
(:func:`greedy_chunk`). ``models/llama.py`` keeps walks of its own until
its three prefills are one program over the pool's pages."""

from collections import namedtuple
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..kernels.paged_attention import seq_local_pages

__all__ = ["PagedPrograms", "prefill_block_rows", "kv_scales_of", "scan_runs",
           "block_window", "walk_blocks", "run_scans", "greedy_chunk"]


@dataclass(frozen=True)
class PagedPrograms:
    """What a model family gives ``DecodeEngine``'s paged mode: its two
    programs, the geometry of its block pool, what a slot holds beside
    its pages, and what it cannot serve.

    ``prefill_paged(stacked, embed, final_norm, lm_head, scales, ids,
    pad_len, table_row, [slot,] *pool)`` -> (first token [1], *pool) and
    ``decode_chunk_paged(stacked, embed, final_norm, lm_head, scales, tok,
    tables, lens, *pool)`` -> (tokens [chunk, b], *pool); the engine jits
    them under these names. ``pool`` is (k pool, v pool[, k scales, v
    scales]) followed by the arrays of ``slot_state``: per-slot state no
    page table describes, one ``ShapeDtypeStruct`` an array for the
    engine's ``slots``. A family with such state is handed the ``slot``
    of the row it prefills, its prefill takes the pool donated, and the
    engine refuses at construction every option in ``unsupported``
    (option -> why). ``v_head_dim``: the width of a value head in the
    pool where it is not a key head's (0: ``head_dim``).
    ``value_pool=False``: the family keeps ONE kind of page (a latent
    that is key and value at once); the engine then builds no second
    pool, ``pool`` is (page pool, *``slot_state``) and a block costs one
    page a layer.
    ``device_counters`` names the entries of the LAST array of
    ``slot_state``, an int32 vector the two programs add to on the
    device (what only the device knows: which experts a step's rows
    chose); the engine hands it over like the rest but does not donate
    it, fetches it in ``stats()`` alone and keeps
    ``engine_<name>_total``. ``host_counters`` (name -> function) is
    what the engine counts itself at every decode launch for a family
    whose decode work is no plain function of the context: each function
    is handed the contexts of the live rows at each of the launch's
    steps (int64 [steps, rows]) and gives what ``engine_<name>_total``
    grows by; the totals ride in each launch's entry behind the device
    counters. ``trace_scopes`` names the ``jax.named_scope``s of the two
    programs that a reader of a device trace should be able to find: a
    trace names an event by its compiled instruction and carries no
    scope, so with ``profile`` on the engine reads each program's
    compiled text once and gives ``stats()["scopes"]``: program ->
    {instruction name: scope}."""
    prefill_paged: object
    decode_chunk_paged: object
    kv_layers: int
    kv_heads: int
    head_dim: int
    slot_state: object = None
    chunks_per_block: int = 0
    unsupported: dict = field(default_factory=dict)
    v_head_dim: int = 0
    value_pool: bool = True
    device_counters: tuple = ()
    host_counters: dict = field(default_factory=dict)
    trace_scopes: tuple = ()


def prefill_block_rows(cfg, s_max):
    """Rows the cold prefill of a configuration runs at a time (what the
    engine hands ``paged_programs`` as ``prefill_block``). 256 is where
    a block's DENSE matmuls cost what reading their bfloat16 weights
    costs (two operations a weight byte a row, against a v5e's 240 a
    byte): a smaller block re-reads the weights for nothing, a larger
    one pads a short prompt for nothing and is no cheaper a row
    (``PERF.md``, Findings PR 33: 128, 256 and 512 on the chip). A
    configuration that holds a SHARE of a router's experts
    (``held_experts`` fewer than ``n_routed_experts``) reads a held
    expert's weights once a block for the rows the router sends it,
    ``rows * num_experts_per_tok / n_routed_experts``: where 256 rows
    bring it fewer than 16, the block is 512 (Findings PR 46). Halved
    until the window holds two blocks."""
    rows = 256
    held = getattr(cfg, "held_experts", None)
    if held is not None and held[1] < cfg.n_routed_experts \
            and rows * cfg.num_experts_per_tok < 16 * cfg.n_routed_experts:
        rows = 512
    while rows > 8 and 2 * rows > s_max:
        rows //= 2
    return rows


def kv_scales_of(pool):
    """The int8 pools' scales ``(kscale, vscale)`` of a paged program's
    ``*pool``, None for float pools."""
    return (pool[2], pool[3]) if len(pool) == 4 else None


def _write_page_ids(page, n_pages, seq_axis):
    """Where a decode step reads and writes its rows' write pages
    ``page`` [b]: (read ids, write ids, scatter mode). On page-sharded
    pools (``seq_axis``, 2-D mesh) ``page`` is a GLOBAL id: reads clamp
    into the local stripe of ``n_pages`` (garbage on non-owners, whose
    writes are dropped) and writes rebase + drop non-owned rows, so the
    update lands exactly once, on the owning shard."""
    if seq_axis is None:
        return page, page, None
    wp, owned = seq_local_pages(page, n_pages, seq_axis)
    return jnp.where(owned, wp, 0), wp, "drop"


def _set_page_row(pages, off, tok):
    """pages [b, kvh, bs, hd] with row ``off[b]`` of row b's page
    replaced by tok [b, kvh, hd]. A select, which fuses into the ops
    around it in the pages' own layout (a scatter along the in-page
    axis asks for another)."""
    slot = jnp.arange(pages.shape[2])[:, None] == off[:, None, None, None]
    return jnp.where(slot, tok[:, :, None, :].astype(pages.dtype), pages)


def _token_insert(pool, layer, page, off, tok, seq_axis=None):
    """Append ONE token per row into layer ``layer`` of a stacked pool
    [L, N, kvh, bs, hd]: page/off [b] int32 write cursors, tok
    [b, kvh, hd]. Written as a read-modify-write of the rows' PAGES
    (b x [kvh, bs, hd], gathered and scattered whole at
    ``[layer, page]``), not as a scatter of b rows of ``hd``: a page is
    what the paged kernel's DMA reads, so the chip's compiler keeps the
    pool in the kernel's layout for both, where a scatter along the
    in-page axis wants a layout of its own and pays for it with a copy
    of the whole pool in every layer. A row's write page is private
    (shared prefix pages are full; copy-on-write clones a partial one
    at admission), so no two live rows collide; inactive rows all land
    on the NULL page, whose content nobody reads. ``seq_axis``:
    :func:`_write_page_ids`."""
    rp, wp, mode = _write_page_ids(page, pool.shape[1], seq_axis)
    pages = _set_page_row(pool[layer, rp], off, tok)  # [b, kvh, bs, hd]
    return pool.at[layer, wp].set(pages, mode=mode)


def _quantized_token_insert(pool, scales, layer, page, off, tok,
                            seq_axis=None):
    """Append ONE token per row into layer ``layer`` of a stacked int8
    pool with a RUNNING-MAX per-(page, kv head) scale (ISSUE 8 int8
    paged KV).

    pool [L, N, kvh, bs, hd] int8 codes; scales [L, N, kvh] f32; layer
    an int32 scalar; page/off [b] int32 write cursors; tok [b, kvh, hd]
    f32. Only the rows' pages are read and written, at ``[layer, page]``
    where the pool lies. The page's scale only
    ever grows (``new = max(old, amax(tok)/127)``), and the resident
    codes are re-expressed in the new scale by ``round(q * old/new)`` —
    when the token doesn't raise the max the ratio is exactly 1.0 and
    ``round(q * 1.0) == q``, so untouched tokens keep their codes
    bit-identical (the no-op case every step but the occasional
    outlier). Inactive rows write the NULL page, same as the fp path.
    ``seq_axis``: :func:`_write_page_ids`."""
    rp, wp, mode = _write_page_ids(page, pool.shape[1], seq_axis)
    amax = jnp.abs(tok).max(axis=-1)                     # [b, kvh]
    old = scales[layer, rp]                              # [b, kvh]
    new = jnp.maximum(old, amax / 127.0)
    codes = pool[layer, rp]                              # [b, kvh, bs, hd]
    ratio = (old / new)[:, :, None, None]
    req = jnp.clip(jnp.round(codes.astype(jnp.float32) * ratio),
                   -127, 127)
    qt = jnp.clip(jnp.round(tok / new[:, :, None]), -127, 127)
    req = _set_page_row(req, off, qt)
    pool = pool.at[layer, wp].set(req.astype(pool.dtype), mode=mode)
    scales = scales.at[layer, wp].set(new, mode=mode)
    return pool, scales


def _row_pages(kc, pad, mb, bs, pack=1):
    """One row's contiguous keys (or values) [L, s, kvh, hd], window
    column ``pad`` holding its first token, as pool pages
    [L, mb, kvh/pack, bs, pack*hd] from context position 0."""
    la, s, kvh, hd = kc.shape
    kc = jnp.roll(kc, -pad, axis=1)
    if s < mb * bs:
        kc = jnp.pad(kc, ((0, 0), (0, mb * bs - s), (0, 0), (0, 0)))
    kc = kc[:, :mb * bs].reshape(la, mb, bs, kvh // pack, pack * hd)
    return jnp.swapaxes(kc, 2, 3)


# ONE right-aligned row's window (``ids`` [total], padded on the left, its
# first token in column ``pad``) and the block ``i`` of it that a walk runs
Window = namedtuple("Window", "ids pad block n_blocks total")
Block = namedtuple("Block", "i start rows positions first")


def block_window(ids, pad_len, block):
    """ids [1, s], pad_len [1] -> ``Window`` (a block: at most s rows)."""
    s = ids.shape[1]
    block = min(block, s)
    n_blocks = -(-s // block)
    total = n_blocks * block
    shift = total - s
    return Window(jnp.pad(ids[0], (shift, 0)), pad_len[0] + shift, block,
                  n_blocks, total)


def walk_blocks(window, embed, init, run_layers, positions=True):
    """The cold prefill's walk over ``window`` from the block of the row's
    first token to the last, where its last token lies: the trip count is
    data, so one compiled program serves every prompt length. ``init()``
    gives what the family carries from block to block (any pytree) as
    the walk starts; ``run_layers(x, state, blk)`` runs the embeddings x
    [block, d] of ``Block`` ``blk`` through the stack and gives (x, state)
    (``positions=False``: a family that turns nothing by position is
    handed none). Returns (state, the last row [1, d])."""
    ids, pad, block, n_blocks, _ = window
    first = pad // block

    def run_block(i, carry):
        state, _ = carry
        start = i * block
        cols = start + jnp.arange(block)
        blk = Block(i, start, cols >= pad,
                    jnp.maximum(cols - pad, 0) if positions else None, first)
        x = jnp.take(embed, jax.lax.dynamic_slice_in_dim(ids, start, block),
                     axis=0)
        x, state = run_layers(x, state, blk)
        return state, x[-1:]

    return jax.lax.fori_loop(
        first, n_blocks, run_block,
        (init(), jnp.zeros((1, embed.shape[1]), embed.dtype)))


def run_scans(runs):
    """A stack as runs of like layers (a configuration's ``runs()``:
    tuples that end in the run's length), one ``lax.scan`` a run: yields
    (run, scan); ``scan(layer, carry, *xs)`` scans ``layer(*run[:-1],
    carry, j, *xs)`` -> (carry, ys) over the run's layers ``j`` = 0..
    (int32, data: a layer indexes the stacked weights where they lie).
    For a stack whose runs carry different things; else :func:`scan_runs`."""
    for run in runs:
        idx = jnp.arange(run[-1], dtype=jnp.int32)
        yield run, lambda layer, carry, *xs, run=run, idx=idx: jax.lax.scan(
            lambda c, x: layer(*run[:-1], c, *x), carry, (idx, *xs))


def scan_runs(runs, layer, carry):
    """``carry`` through every run's scan (:func:`run_scans`) in turn."""
    for _, scan in run_scans(runs):
        carry, _ = scan(layer, carry)
    return carry


def greedy_chunk(step, head, chunk, tok, tables, lens, pool):
    """``chunk`` greedy steps of one token per slot: ``step(*head, tok,
    tables, lens, pool, live)`` -> (float32 logits [b, V], pool) at
    contexts ``lens`` [b]; ``live`` [b]: the slots that hold a row as the
    chunk starts (``lens`` 0: none). Returns (tokens [chunk, b], *pool)."""
    live = lens > 0

    def body(carry, i):
        tok, pool = carry
        logits, pool = step(*head, tok, tables, lens + i, pool, live)
        nxt = jnp.argmax(logits, axis=-1)
        return (nxt, pool), nxt

    (tok, pool), toks = jax.lax.scan(body, (tok, pool), jnp.arange(chunk))
    return (toks, *pool)

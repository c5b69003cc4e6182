"""MiMo-V2 family (``model_type: mimo_v2``; MiMo-V2-Flash, MiMo-V2.5): a
decoder whose ``hybrid_layer_pattern`` mixes layers of full ("global")
attention with layers that see a sliding window of ``sliding_window``
tokens through a learned sink logit per head, and whose
``moe_layer_freq`` mixes dense SwiGLU layers with layers of routed
experts (sigmoid scores with a selection bias, top-k, weights
renormalised over the chosen, no shared expert). Query and key heads are
``head_dim`` wide, value heads ``v_head_dim``; the rotary embedding turns
the first ``int(head_dim * partial_rotary_factor)`` dimensions, with a
base of its own for each kind of layer; the two kinds have kv head
counts of their own. Serving only, through the paged ``DecodeEngine``.

A chip may hold a SHARE of the experts (``held_experts = (first,
count)``: one rank of an expert-parallel deployment). The router keeps
its ``n_routed_experts`` outputs and its experts per token; the chip
computes its own experts' part of the result for the (token, expert)
pairs routed to them (``fleet.moe.moe_route_held``), and what the absent
experts would add is left out. The held experts of all expert layers lie
in ONE stack ``[layers * count, ...]``: a layer's grouped products name
their experts by their place in it, so no launch is handed a slice of
the expert weights.

What a slot of the engine holds is of two kinds. The global layers'
keys and values are pages of the engine's block pool (keys padded from
``head_dim`` to whole lane tiles, ``head_lanes``). A window layer never reads past
``sliding_window`` tokens, so its keys and values are **per-slot state
of fixed size**: a ring ``[window layers, slots, kv heads, window,
width]`` written at ``position % window``, beside the pool, donated
through both programs. Behind the rings rides one small int32 vector,
the counters only the device can keep (pairs routed to held experts,
held experts visited, expert-layer passes whose products took the whole
stream and not its head).

The stack is driven by the two patterns as data: runs of like layers are
scanned, each run indexing the stacked weights where they lie.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.fleet.moe import moe_held_ffn
from ..kernels.paged_attention import paged_decode_attention
from .llama import _rms, _rope
from .paged_stack import (PagedPrograms, _row_pages, _token_insert,
                          block_window, greedy_chunk, run_scans, scan_runs,
                          walk_blocks)

__all__ = ["MimoV2Config", "MimoV2ForCausalLM", "MIMO_V2_PRESETS"]

_NEG = -1e30


@dataclass
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384          # a dense layer's SwiGLU
    moe_intermediate_size: int = 2048       # one expert's
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4            # global layers
    swa_num_key_value_heads: int = 8        # window layers
    head_dim: int = 192
    v_head_dim: int = 128
    hybrid_layer_pattern: tuple = ()        # per layer: 0 global, 1 window
    moe_layer_freq: tuple = ()              # per layer: 0 dense, 1 experts
    n_routed_experts: int = 256             # the router's outputs
    held_experts: tuple = None              # (first, count) held here
    num_experts_per_tok: int = 8
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = None
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        n = self.num_hidden_layers
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            pat = getattr(self, name)
            if len(pat) != n or any(v not in (0, 1) for v in pat):
                raise ValueError(f"{name} must hold 0 or 1 for each of the "
                                 f"{n} layers, got {pat!r}")
        if self.held_experts is None:
            self.held_experts = (0, self.n_routed_experts)
        first, count = self.held_experts = tuple(self.held_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held_experts={self.held_experts!r} is no "
                             f"share of {self.n_routed_experts} experts")
        # keys the published mimo_v2 configurations give one value
        for name, value in (("add_swa_attention_sink_bias", True),
                            ("add_full_attention_sink_bias", False),
                            ("norm_topk_prob", True),
                            ("routed_scaling_factor", None)):
            if getattr(self, name) != value:
                raise ValueError(
                    f"MimoV2 supports {name}={value!r} alone (the published "
                    f"mimo_v2 configurations), got {getattr(self, name)!r}")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary width {self.rotary_dim} of head_dim "
                             f"{self.head_dim}")

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def head_lanes(self):
        """The width of a key head in the page pool: ``head_dim`` rounded
        up to whole lane tiles, zero past ``head_dim`` (which adds nothing
        to a score). The chip lays a head of 192 out 256 lanes wide in
        any case, and the decode kernel's page copy has to end on a
        tile."""
        return -(-self.head_dim // 128) * 128

    def kinds(self):
        """Per layer: (``global`` | ``window``, ``dense`` | ``moe``)."""
        return [("window" if a else "global", "moe" if f else "dense")
                for a, f in zip(self.hybrid_layer_pattern,
                                self.moe_layer_freq)]

    def count(self, kind):
        return sum(kind in k for k in self.kinds())

    def runs(self):
        """The stack as runs of like layers: (attention kind, ffn kind,
        first layer, first index within the attention kind, first index
        within the ffn kind, length)."""
        out, seen = [], {"global": 0, "window": 0, "dense": 0, "moe": 0}
        for l, (a, f) in enumerate(self.kinds()):
            if out and out[-1][:2] == [a, f]:
                out[-1][5] += 1
            else:
                out.append([a, f, l, seen[a], seen[f], 1])
            seen[a] += 1
            seen[f] += 1
        return [tuple(r) for r in out]


MIMO_V2_PRESETS = {
    # the published stack's first period behind its dense layer, at
    # debug widths: 16 experts of which 4 are held, heads of 24 / 16
    "debug": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=7,
                  num_attention_heads=4, num_key_value_heads=1,
                  swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
                  hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
                  moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
                  n_routed_experts=16, held_experts=(0, 4),
                  num_experts_per_tok=2, sliding_window=8),
}

_NORMS = ("input_ln", "post_ln")
# a layer's attention leaves under the names its code reads -> the stacks
_ATTN = {"global": {"wq": "wq_g", "wk": "wk_g", "wv": "wv_g", "wo": "wo_g"},
         "window": {"wq": "wq_w", "wk": "wk_w", "wv": "wv_w", "wo": "wo_w",
                    "sink": "sink"}}
_FFN = {"dense": ("w_gate", "w_up", "w_down"),
        "moe": ("router", "router_bias")}
_EXPERTS = ("we_gate", "we_up", "we_down")      # never indexed by layer


def _layer_params(w, a_kind, f_kind, l, a, f):
    """Layer ``l``'s leaves under short names: its norms, the attention
    leaves at index ``a`` of their kind, the ffn leaves at ``f`` of
    theirs (all three are data)."""
    lp = {n: w[n][l] for n in _NORMS}
    lp.update({n: w[stack][a] for n, stack in _ATTN[a_kind].items()})
    lp.update({n: w[n][f] for n in _FFN[f_kind]})
    return lp


def _qkv(cfg, lp, h, positions, theta):
    """h [n, d] at ``positions`` [n] -> q [n, H, hd], k [n, kvh, hd]
    (both with the first ``rotary_dim`` dimensions turned), v
    [n, kvh, hdv] scaled by ``attention_value_scale``. The barrier keeps
    a projection a plain [n, d] x [d, columns] product: with the split
    into heads folded into it, the chip's compiler re-laid the whole
    stack of ``wq`` at every launch and copied a layer's slice of it in
    every step."""
    hd, hdv, rot = cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim
    n = h.shape[0]

    def turn(w):
        x = jax.lax.optimization_barrier(h @ w).reshape(n, -1, hd)
        r = _rope(x[None, :, :, :rot], positions[None], theta, rot)[0]
        return jnp.concatenate([r, x[..., rot:]], axis=-1)

    v = jax.lax.optimization_barrier(h @ lp["wv"]).reshape(n, -1, hdv)
    return (turn(lp["wq"]), turn(lp["wk"]),
            v * jnp.asarray(cfg.attention_value_scale, v.dtype))


def _ffn(cfg, w, lp, f_kind, f, x, rows, counts):
    """x + ffn(rms(x)); an expert layer routes over all the router's
    experts and computes the held ones' part (``fleet.moe.moe_held_ffn``:
    ``rows`` [n] marks real tokens, ``counts`` int32 [4] gains)."""
    y = _rms(x, lp["post_ln"], cfg.layernorm_epsilon)
    if f_kind == "dense":
        return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
            @ lp["w_down"], counts
    out, counts = moe_held_ffn(
        y, lp["router"], lp["router_bias"], [w[n] for n in _EXPERTS], f,
        rows, counts, top_k=cfg.num_experts_per_tok, held=cfg.held_experts,
        scoring=cfg.scoring_func)
    return x + out.astype(x.dtype), counts


def _softmax_with_sink(s, ok, sink):
    """Probabilities of float32 scores ``s`` [.., t] where ``ok``
    (broadcastable) holds, beside a further logit a head, ``sink``
    (broadcastable to ``s`` without its last axis), that takes
    probability and gives no value."""
    s = jnp.where(ok, s, _NEG)
    m = jnp.maximum(s.max(axis=-1), sink)
    p = jnp.where(ok, jnp.exp(s - m[..., None]), 0.0)
    return p / (p.sum(axis=-1) + jnp.exp(sink - m))[..., None]


def _window_block(cfg, lp, q, k, v, pk, pv, start, pad):
    """A window layer over one block of a cold prefill: queries q
    [blk, H, hd] at columns ``start..`` attend to the block's own keys
    and to the ``sliding_window`` tokens before it (pk, pv, carried from
    the last block), each to the window that ends at its own position;
    columns before ``pad`` hold no token."""
    blk, win = q.shape[0], cfg.sliding_window
    kvh = k.shape[1]
    keys = jnp.concatenate([pk, k], axis=0)             # [win + blk, ..]
    vals = jnp.concatenate([pv, v], axis=0)
    qh = q.reshape(blk, kvh, -1, cfg.head_dim)
    s = jnp.einsum("sngd,tnd->ngst", qh, keys).astype(jnp.float32)
    qcol = start + jnp.arange(blk)[:, None]
    kcol = start - win + jnp.arange(win + blk)[None, :]
    ok = (kcol <= qcol) & (kcol > qcol - win) & (kcol >= pad)
    p = _softmax_with_sink(s / cfg.head_dim ** 0.5, ok[None, None],
                           lp["sink"].reshape(kvh, -1, 1))
    o = jnp.einsum("ngst,tnd->sngd", p.astype(q.dtype), vals)
    return o.reshape(blk, -1) @ lp["wo"], keys[-win:], vals[-win:]


def _global_block(cfg, lp, q, k, v, kc, vc, start, pad, first, i):
    """A global layer over one block of a cold prefill: causal inside
    the block, plus the row's earlier keys, read from the contiguous
    carry kc [s, kvh, hd], vc [s, kvh, hdv] block by block from the
    first block run (``first``) to this one (``i``): online softmax,
    scores [blk, blk] at a time, float32."""
    blk = q.shape[0]
    kvh = k.shape[1]
    qh = q.reshape(blk, kvh, -1, cfg.head_dim)
    g = qh.shape[2]
    scale = cfg.head_dim ** 0.5

    def fold(state, kh, vh, ok):
        m, l, acc = state
        s = jnp.einsum("sngd,tnd->ngst", qh, kh).astype(jnp.float32)
        s = jnp.where(ok, s / scale, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        pv_ = jnp.einsum("ngst,tnd->ngsd", p.astype(q.dtype), vh)
        return (m_new, alpha * l + p.sum(axis=-1),
                alpha[..., None] * acc + pv_.astype(jnp.float32))

    cols = jnp.arange(blk)
    own = (cols[None, :] <= cols[:, None]) & (start + cols[None, :] >= pad)
    state = fold((jnp.full((kvh, g, blk), _NEG, jnp.float32),
                  jnp.zeros((kvh, g, blk), jnp.float32),
                  jnp.zeros((kvh, g, blk, cfg.v_head_dim), jnp.float32)),
                 k, v, own[None, None])

    def body(j, state):
        at = j * blk
        ok = (at + cols >= pad)[None, None, None, :]
        return fold(state, jax.lax.dynamic_slice_in_dim(kc, at, blk, 0),
                    jax.lax.dynamic_slice_in_dim(vc, at, blk, 0), ok)

    _, l, acc = jax.lax.fori_loop(first, i, body, state)
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return jnp.moveaxis(o, 2, 0).reshape(blk, -1) @ lp["wo"]


def _prefill(cfg, w, embed, final_norm, lm_head, ids, pad_len, table_row,
             slot, pool, block):
    """The cold prefill of ONE right-aligned row (ids [1, s], pad_len
    [1]); ``paged_stack.walk_blocks`` has the walk. The family's own: a
    global layer reads the row's earlier keys from a contiguous carry, a
    window layer the block and the ``sliding_window`` tokens before it,
    carried from block to block; at the window's end the global layers'
    keys and values go page by page through ``table_row``, the window
    layers' last ``sliding_window`` tokens into ``slot`` of the rings at
    their positions modulo the ring. Returns (float32 logits, pool)."""
    kp, vp, rk, rv, counts = pool
    window = block_window(ids, pad_len, block)
    total, pad, win = window.total, window.pad, cfg.sliding_window
    dtype = embed.dtype
    ng, nw = cfg.count("global"), cfg.count("window")
    kvg, kvw = cfg.num_key_value_heads, cfg.swa_num_key_value_heads
    hd, hdv = cfg.head_dim, cfg.v_head_dim

    def run_layers(x, state, blk):
        kc, vc, pk, pv, counts = state

        def global_layer(_, f_kind, l0, a0, f0, carry, j):
            x, kc, vc, counts = carry
            a = a0 + j
            lp = _layer_params(w, "global", f_kind, l0 + j, a, f0 + j)
            h = _rms(x, lp["input_ln"], cfg.layernorm_epsilon)
            q, k, v = _qkv(cfg, lp, h, blk.positions, cfg.rope_theta)
            x = x + _global_block(cfg, lp, q, k, v, kc[a], vc[a], blk.start,
                                  pad, blk.first, blk.i)
            kc = jax.lax.dynamic_update_slice(
                kc, k[None], (a, blk.start, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None], (a, blk.start, 0, 0))
            x, counts = _ffn(cfg, w, lp, f_kind, f0 + j, x, blk.rows, counts)
            return (x, kc, vc, counts), None

        def window_layer(_, f_kind, l0, a0, f0, carry, j, pkl, pvl):
            x, counts = carry
            lp = _layer_params(w, "window", f_kind, l0 + j, a0 + j, f0 + j)
            h = _rms(x, lp["input_ln"], cfg.layernorm_epsilon)
            q, k, v = _qkv(cfg, lp, h, blk.positions, cfg.swa_rope_theta)
            o, pkl, pvl = _window_block(cfg, lp, q, k, v, pkl, pvl,
                                        blk.start, pad)
            x, counts = _ffn(cfg, w, lp, f_kind, f0 + j, x + o, blk.rows,
                             counts)
            return (x, counts), (pkl, pvl)

        prev = []
        for (a_kind, _, _, a0, _, n), scan in run_scans(cfg.runs()):
            if a_kind == "global":
                (x, kc, vc, counts), _ = scan(global_layer,
                                              (x, kc, vc, counts))
            else:
                (x, counts), kept = scan(window_layer, (x, counts),
                                         pk[a0:a0 + n], pv[a0:a0 + n])
                prev.append(kept)
        pk, pv = (jnp.concatenate(kept) for kept in zip(*prev))
        return x, (kc, vc, pk, pv, counts)

    (kc, vc, pk, pv, counts), last = walk_blocks(
        window, embed,
        lambda: (jnp.zeros((ng, total, kvg, hd), dtype),
                 jnp.zeros((ng, total, kvg, hdv), dtype),
                 jnp.zeros((nw, win, kvw, hd), dtype),
                 jnp.zeros((nw, win, kvw, hdv), dtype), counts),
        run_layers)
    logits = _logits(cfg, last, final_norm, lm_head)
    mb, bs = table_row.shape[0], kp.shape[-2]
    kc = jnp.pad(kc, ((0, 0),) * 3 + ((0, kp.shape[-1] - hd),))
    kp = kp.at[:, table_row].set(_row_pages(kc, pad, mb, bs))
    vp = vp.at[:, table_row].set(_row_pages(vc, pad, mb, bs))
    # the carry's column c holds position c + (total - win - pad): into
    # the ring at its position modulo the ring (what lies before the
    # row's first token lands where no position yet reads)
    into = lambda prev: jnp.swapaxes(
        jnp.roll(prev, total - win - pad, axis=1), 1, 2)[:, None]
    rk = jax.lax.dynamic_update_slice(rk, into(pk), (0, slot, 0, 0, 0))
    rv = jax.lax.dynamic_update_slice(rv, into(pv), (0, slot, 0, 0, 0))
    return logits, (kp, vp, rk, rv, counts)


def _logits(cfg, x, final_norm, lm_head):
    x = _rms(x, final_norm, cfg.layernorm_epsilon)
    return (x @ lm_head).astype(jnp.float32)


def _global_decode(cfg, lp, x, a, kp, vp, tables, lens):
    """A global layer for one token per slot against layer ``a`` of the
    paged pools: keys and queries padded to the pool's lane tiles (the
    read divides by the square root of the width it is handed)."""
    b = x.shape[0]
    bs, wide = kp.shape[-2], kp.shape[-1]
    h = _rms(x, lp["input_ln"], cfg.layernorm_epsilon)
    q, k, v = _qkv(cfg, lp, h, lens, cfg.rope_theta)
    widen = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, wide - cfg.head_dim)))
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    off = lens % bs
    kp = _token_insert(kp, a, page, off, widen(k))
    vp = _token_insert(vp, a, page, off, v)
    scale = (wide / cfg.head_dim) ** 0.5
    qg = widen((q.astype(jnp.float32) * scale).astype(x.dtype)).reshape(
        b, k.shape[1], -1, wide)
    o = paged_decode_attention(qg, kp, vp, tables, lens + 1, a,
                               name=f"paged_decode_qk{cfg.head_dim}")
    return o.reshape(b, -1).astype(x.dtype) @ lp["wo"], kp, vp


def _window_decode(cfg, lp, x, a, rk, rv, lens):
    """A window layer for one token per slot: the token's key and value
    go into the slot's ring at ``position % window``, and the read is
    plain attention over the ring (what a ring entry holds is the latest
    position congruent to it: before the row's first token, nothing)
    with the sink. No page table."""
    b = x.shape[0]
    win = rk.shape[3]
    h = _rms(x, lp["input_ln"], cfg.layernorm_epsilon)
    q, k, v = _qkv(cfg, lp, h, lens, cfg.swa_rope_theta)
    entry = jnp.arange(win)
    here = (entry[None, :] == (lens % win)[:, None])[:, None, :, None]
    with jax.named_scope("swa_ring_decode"):
        keys = jnp.where(here, k[:, :, None, :], rk[a])  # [b, kvh, win, hd]
        vals = jnp.where(here, v[:, :, None, :], rv[a])
        rk = jax.lax.dynamic_update_index_in_dim(rk, keys, a, 0)
        rv = jax.lax.dynamic_update_index_in_dim(rv, vals, a, 0)
        kvh = keys.shape[1]
        qh = q.reshape(b, kvh, -1, cfg.head_dim)
        s = jnp.einsum("bngd,bntd->bngt", qh, keys).astype(jnp.float32)
        held = lens[:, None] - (lens[:, None] - entry[None, :]) % win
        p = _softmax_with_sink(s / cfg.head_dim ** 0.5,
                               (held >= 0)[:, None, None, :],
                               lp["sink"].reshape(1, kvh, -1))
        o = jnp.einsum("bngt,bntd->bngd", p.astype(x.dtype), vals)
    return o.reshape(b, -1) @ lp["wo"], rk, rv


def _decode_step(cfg, w, embed, final_norm, lm_head, tok, tables, lens,
                 pool, live):
    """One token per slot through the whole stack: tok [b] -> (float32
    logits [b, V], pool); pool = (kp, vp, ring k, ring v, counters)."""
    x = jnp.take(embed, tok, axis=0)

    def layer(a_kind, f_kind, l0, a0, f0, carry, j):
        x, (kp, vp, rk, rv, counts) = carry
        lp = _layer_params(w, a_kind, f_kind, l0 + j, a0 + j, f0 + j)
        if a_kind == "global":
            o, kp, vp = _global_decode(cfg, lp, x, a0 + j, kp, vp, tables,
                                       lens)
        else:
            o, rk, rv = _window_decode(cfg, lp, x, a0 + j, rk, rv, lens)
        x, counts = _ffn(cfg, w, lp, f_kind, f0 + j, x + o, live, counts)
        return (x, (kp, vp, rk, rv, counts)), None

    x, pool = scan_runs(cfg.runs(), layer, (x, tuple(pool)))
    return _logits(cfg, x, final_norm, lm_head), pool


def leaf_shapes(cfg):
    """name -> (shape, kind of leaf) of every parameter. The sink logits,
    the router and its selection bias are float32 whatever the model's
    dtype: a near-tie between two experts' scores is settled in the
    precision the scores are stated in."""
    d, ff, fe = (cfg.hidden_size, cfg.intermediate_size,
                 cfg.moe_intermediate_size)
    H, hd, hdv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    L, E = cfg.num_hidden_layers, cfg.n_routed_experts
    nd, ne = cfg.count("dense"), cfg.count("moe")
    held = ne * cfg.held_experts[1]
    out = {"embed_tokens": ((cfg.vocab_size, d), "matrix"),
           "input_ln": ((L, d), "one"), "post_ln": ((L, d), "one")}
    for tag, n, kvh in (("g", cfg.count("global"), cfg.num_key_value_heads),
                        ("w", cfg.count("window"),
                         cfg.swa_num_key_value_heads)):
        out.update({f"wq_{tag}": ((n, d, H * hd), "matrix"),
                    f"wk_{tag}": ((n, d, kvh * hd), "matrix"),
                    f"wv_{tag}": ((n, d, kvh * hdv), "matrix"),
                    f"wo_{tag}": ((n, H * hdv, d), "matrix")})
    out.update({"sink": ((cfg.count("window"), H), "sink"),
                "w_gate": ((nd, d, ff), "matrix"),
                "w_up": ((nd, d, ff), "matrix"),
                "w_down": ((nd, ff, d), "matrix"),
                "router": ((ne, d, E), "router"),
                "router_bias": ((ne, E), "bias"),
                "we_gate": ((held, d, fe), "matrix"),
                "we_up": ((held, d, fe), "matrix"),
                "we_down": ((held, fe, d), "matrix"),
                "final_norm": ((d,), "one"),
                "lm_head": ((d, cfg.vocab_size), "matrix")})
    return out


class MimoV2ForCausalLM(nn.Layer):
    """Stacked-parameter MiMo-V2: norms stacked over all layers, the
    attention leaves over the layers of their kind, the dense SwiGLU and
    the routers over theirs, and the held experts of every expert layer
    in one stack ``[expert layers * held, ...]``."""

    def __init__(self, config: MimoV2Config | str = "debug"):
        super().__init__()
        if isinstance(config, str):
            config = MimoV2Config(**MIMO_V2_PRESETS[config])
        self.config = cfg = config
        from ..nn import initializer as I
        inits = {"matrix": I.Normal(0.0, 0.02), "one": I.Constant(1.0),
                 "sink": I.Normal(0.0, 1.0), "router": I.Normal(0.0, 0.1),
                 "bias": I.Uniform(-0.05, 0.05)}
        for name, (shape, how) in leaf_shapes(cfg).items():
            p = self.create_parameter(shape=list(shape),
                                      default_initializer=inits[how])
            if cfg.dtype != "float32" and how in ("matrix", "one"):
                p._in_place_update(p._value.astype(cfg.dtype))
            self.add_parameter(name, p)

    def _stacked_names(self):
        return [*_NORMS, *_ATTN["global"].values(),
                *_ATTN["window"].values(), *_FFN["dense"], *_FFN["moe"],
                *_EXPERTS]

    def forward(self, input_ids):
        raise NotImplementedError(
            "MimoV2ForCausalLM is served through DecodeEngine "
            "(paged_programs); it has no cache-free forward")

    def paged_programs(self, chunk, prefill_block, mp_axis=None,
                       seq_axis=None, n_seq=1):
        """What ``DecodeEngine`` binds for this family."""
        cfg = self.config
        win = cfg.sliding_window
        if prefill_block < win:
            raise ValueError(
                f"the cold prefill's block of {prefill_block} rows is "
                f"shorter than sliding_window={win}")

        def prefill_paged(stacked, embed, fnorm, lm, scales, ids, pad_len,
                          table_row, slot, *pool):
            """ids [1, s_max] right-aligned; the row's global keys and
            values go into its pages, its window layers' last tokens
            into ``slot`` of the rings, inside the program."""
            logits, pool = _prefill(cfg, stacked, embed, fnorm, lm, ids,
                                    pad_len, table_row, slot, pool,
                                    prefill_block)
            return (jnp.argmax(logits, axis=-1), *pool)

        def decode_chunk_paged(stacked, embed, fnorm, lm, scales, tok,
                               tables, lens, *pool):
            """One chunk; a slot with ``lens == 0`` holds no row: its
            tokens are routed to no expert and counted nowhere."""
            return greedy_chunk(_decode_step, (cfg, stacked, embed, fnorm, lm),
                                chunk, tok, tables, lens, pool)

        dtype = jnp.dtype(cfg.dtype)
        nw, kvw = cfg.count("window"), cfg.swa_num_key_value_heads
        return PagedPrograms(
            prefill_paged=prefill_paged,
            decode_chunk_paged=decode_chunk_paged,
            kv_layers=cfg.count("global"),
            kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_lanes, v_head_dim=cfg.v_head_dim,
            slot_state=lambda slots: (
                jax.ShapeDtypeStruct((nw, slots, kvw, win, cfg.head_dim),
                                     dtype),
                jax.ShapeDtypeStruct((nw, slots, kvw, win, cfg.v_head_dim),
                                     dtype),
                jax.ShapeDtypeStruct((4,), jnp.int32)),
            device_counters=("moe_pairs", "moe_expert_visits",
                             "moe_full_stream", "moe_stream_rows"),
            unsupported={
                "prefix_cache": "a prefix hit needs the window layers' "
                                "last keys and values at the page "
                                "boundary, and no snapshot is kept",
                "paged=False": "the window layers' rings live per slot "
                               "beside the page pool",
                "chunked_prefill": "a prompt's chunks would have to "
                                   "carry the window between steps",
                "spec_decode": "a rejected draft would have to roll the "
                               "rings back",
                "kv_dtype='int8'": "the pool's key and value heads "
                                   "differ in width",
                "mesh": "the rings and the held experts have no "
                        "sharding rule"})

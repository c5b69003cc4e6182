"""GLM-5 family (``model_type: glm_moe_dsa``): a decoder whose attention
keeps ONE low-rank latent a token and layer for all its heads (multi-head
latent attention) and reads, at every position, only the ``index_topk``
earlier tokens that a second, small attention (the indexer) scores
highest; the first ``first_k_dense_replace`` layers are dense SwiGLU, the
others routed experts (sigmoid scores with a selection bias, top-k,
weights renormalised over the chosen and scaled by
``routed_scaling_factor``) beside one shared expert. Serving only,
through the paged ``DecodeEngine``.

With ``n = RMSNorm(h)`` at a layer's entry (no biases anywhere but the
indexer key's LayerNorm):

    query    c_q = RMSNorm(n W_dq) [q_lora_rank]; q = c_q W_uq -> H heads
             of q_nope [qk_nope_head_dim] | q_rope [qk_rope_head_dim]
    latent   [c_kv | k_r] = n W_dkv [kv_lora_rank | qk_rope_head_dim];
             c_kv = RMSNorm(c_kv); k_r is one rotary key for all heads;
             [k_nope | v] = c_kv W_ukv -> H heads of qk_nope_head_dim |
             v_head_dim
    rope     theta ``rope_theta``, interleaved pairs (x0,x1),(x2,x3),..,
             on q_rope and k_r
    scores   s[t,u] = (q_nope[t].k_nope[u] + q_rope[t].k_r[u])
             / sqrt(qk_nope_head_dim + qk_rope_head_dim); softmax over the
             ALLOWED u; o = sum p v; out = concat(o) W_o
    indexer  qI = c_q W_qI -> index_n_heads heads of index_head_dim;
             kI = LayerNorm(n W_kI) [index_head_dim, gain and bias]; rope
             (interleaved) on the first qk_rope_head_dim of each;
             w = n W_w [index_n_heads] x index_n_heads^-0.5 x
             index_head_dim^-0.5; I[t,u] = sum_j w[t,j] relu(qI[t,j].kI[u])
             for u <= t; the allowed set of t is the index_topk largest
             I[t,.] (every u <= t while t < index_topk)
    experts  y = shared(n') + routed_scaling_factor x sum over the chosen
             of g_e expert_e(n'); scores = sigmoid(n' W_r) in float32,
             top-k of scores + e_score_correction_bias, g the scores
             renormalised over the chosen

(the indexer is the published DeepSeek-V3.2-Exp lightning indexer, which
``glm_moe_dsa`` names by its ``index_*`` keys; its Hadamard rotation of
qI and kI is orthogonal and drops out of the product; its FP8 cache of
kI is not modelled: bfloat16). The multi-token-prediction layer adds no
term to the next token's logits and is not held.

What the program computes is the ABSORBED form of the same scores:
``q_nope[t].k_nope[u] = (q_nope[t] W_uk^T).c_kv[u]`` and ``sum p v =
(sum p c_kv) W_uv`` a head, so that a query meets the cached latent as it
lies, 64 heads against one ``kv_lora_rank + qk_rope_head_dim`` wide key
and one ``kv_lora_rank`` wide value a token. The model therefore holds
``W_ukv``'s two halves a head as leaves of their own (``w_uk`` [H, nope,
rank], ``w_uv`` [H, rank, v]).

What a slot of the engine holds are two caches of different kind under
the engine's ONE block table, neither of them per-head keys and values:
the engine's key pool carries the **latent page** (one "head" of
``[c_kv | k_r]`` padded to whole lane tiles, ``latent_lanes``) and its
value pool the **indexer's key page** (one "head" of
``index_head_dim``). There is no per-slot state besides the device
counters' vector, so allocation, eviction and preemption are the
allocator's.

What a decode step pays for follows what its slots hold, which the
program reads from its own arguments (``lens``, 0 = no row, and the
table): the live rows are walked one by one and a slot without a row is
not visited; a live row is scored over the least of a few widths
that covers its context (``decode_widths``: 4 x ``index_topk`` columns,
doubled up to the table's length), its ``index_topk`` best are found by
counting passes and not by a sort (``_row_chosen``: the prefill's
``_chosen_mask`` for one row, on the chip one launch of
``kernels/topk_mask.py``), and the absorbed query either passes once
over the row's own latent pages under that mask or, for a row of
``GATHER_FROM`` columns or more, reads the chosen latents alone, fetched
by their columns. The widest way is the same path at the table's
length.

A chip may hold a SHARE of the experts (``held_experts = (first,
count)``), as ``models/mimo_v2.py`` does: the held experts of all expert
layers lie in ONE stack, the router keeps all its outputs, and what the
absent experts would add is left out; the shared expert is computed
whole.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..distributed.fleet.moe import moe_held_ffn
from ..kernels import topk_mask
from ..kernels.latent_attention import (latent_prefill_pallas,
                                        prefill_kernel_serves)
from .llama import _rms
from .paged_stack import (PagedPrograms, _row_pages, _token_insert,
                          block_window, greedy_chunk, scan_runs, walk_blocks)

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "GLM_MOE_DSA_PRESETS"]

_NEG = -1e30
_LANES = 128
SCORE_KEYS = 4096       # keys a piece of the indexer's scores takes at most
ATTEND_KEYS = 2048      # keys a piece of the causal pass takes at most
# columns from which a decode row's chosen latents are fetched by index;
# a narrower row's latent pages are passed over once under the mask,
# which costs less than the fetch up to here (PERF.md, Findings PR 43)
GATHER_FROM = 32768


def check_stack(cfg):
    """What every configuration of a latent family with a held share of
    experts has to hold: ``held_experts`` (first, count), by default all
    the router's, a share of them; the leading dense layers within the
    stack; an even rotary width."""
    if cfg.held_experts is None:
        cfg.held_experts = (0, cfg.n_routed_experts)
    first, count = cfg.held_experts = tuple(cfg.held_experts)
    if not (0 <= first and count >= 1
            and first + count <= cfg.n_routed_experts):
        raise ValueError(f"held_experts={cfg.held_experts!r} is no "
                         f"share of {cfg.n_routed_experts} experts")
    if not 0 <= cfg.first_k_dense_replace <= cfg.num_hidden_layers:
        raise ValueError(
            f"first_k_dense_replace={cfg.first_k_dense_replace} of "
            f"{cfg.num_hidden_layers} layers")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError(f"rotary width {cfg.qk_rope_head_dim}")


@dataclass
class GlmMoeDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288          # a dense layer's SwiGLU
    moe_intermediate_size: int = 2048       # one expert's, routed or shared
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    num_key_value_heads: int = 64           # the expanded form's; unused
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256             # the router's outputs
    held_experts: tuple = None              # (first, count) held here
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 1e6
    rope_type: str = "default"
    rms_norm_eps: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        check_stack(self)
        # keys the published glm_moe_dsa configurations give one value
        for name, value in (("n_group", 1), ("topk_group", 1),
                            ("scoring_func", "sigmoid"),
                            ("rope_type", "default"),
                            ("norm_topk_prob", True)):
            if getattr(self, name) != value:
                raise ValueError(
                    f"GlmMoeDsa supports {name}={value!r} alone (the "
                    f"published glm_moe_dsa configurations), got "
                    f"{getattr(self, name)!r}")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"rotary width {self.qk_rope_head_dim} of index_head_dim "
                f"{self.index_head_dim}")

    @property
    def logit_divisor(self):
        """What a score is divided by ahead of the softmax."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** 0.5

    def inv_freq(self):
        """The rotary frequencies of the ``qk_rope_head_dim`` turned
        dimensions, float32 [qk_rope_head_dim / 2]."""
        return rope_inv_freq(self.qk_rope_head_dim, self.rope_theta)

    @property
    def latent_dim(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self):
        """The width of a token's latent in the page pool: ``[c_kv |
        k_r]`` rounded up to whole lane tiles, zero past it (which adds
        nothing to a score)."""
        return -(-self.latent_dim // _LANES) * _LANES

    @property
    def n_moe(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    def runs(self):
        """The stack as runs of like layers: (ffn kind, first layer,
        length); a layer's index within its kind is its distance from
        the run's first."""
        k, n = self.first_k_dense_replace, self.num_hidden_layers
        return [r for r in (("dense", 0, k), ("moe", k, n - k)) if r[2]]


GLM_MOE_DSA_PRESETS = {
    # one dense layer and four expert layers at debug widths: 16 experts
    # of which 4 are held, the top 8 of the indexer's scores
    "debug": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=5,
                  first_k_dense_replace=1, num_attention_heads=4,
                  q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=24,
                  qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
                  index_head_dim=16, index_topk=8, n_routed_experts=16,
                  held_experts=(0, 4), num_experts_per_tok=2,
                  n_shared_experts=1),
}

_INDEXER = ("w_qi", "w_ki", "ki_ln_g", "ki_ln_b", "w_wi")
_ATTN = ("input_ln", "post_ln", "w_dq", "q_ln", "w_uq", "w_dkv", "kv_ln",
         "w_uk", "w_uv", "wo", *_INDEXER)
_FFN = {"dense": ("w_gate", "w_up", "w_down"),
        "moe": ("router", "router_bias", "ws_gate", "ws_up", "ws_down")}
_EXPERTS = ("we_gate", "we_up", "we_down")      # never indexed by layer


def _layer_params(w, f_kind, l, f):
    """Layer ``l``'s leaves under their names: the attention's and the
    norms' at ``l`` (the indexer's where the family has one), the ffn's
    at index ``f`` of its kind (both data)."""
    lp = {n: w[n][l] for n in _ATTN if n in w}
    lp.update({n: w[n][f] for n in _FFN[f_kind]})
    return lp


def rope_inv_freq(r, theta, yarn=None):
    """The frequencies of ``r`` turned dimensions, float32 [r / 2]:
    ``theta^(-2i/r)``; with ``yarn`` = (factor, original context,
    beta_fast, beta_slow) YaRN's blend of them with the same divided by
    ``factor``: dimension ``i`` takes the divided one by ``clip((i -
    low) / (high - low), 0, 1)``, ``low`` and ``high`` the dimensions
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context (rounded down and up)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    if yarn is None:
        return freqs
    factor, original, beta_fast, beta_slow = yarn
    low, high = yarn_range(r, theta, original, beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_range(r, theta, original, beta_fast, beta_slow):
    """(low, high): the turned dimensions between which YaRN blends."""
    at = lambda turns: r * math.log(original / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), r - 1))


def yarn_mscale(factor, mscale):
    """YaRN's magnitude correction ``0.1 mscale ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_pairs(x, positions, freqs):
    """x [n, .., r] with every dimension turned: interleaved pairs
    (x0,x1),(x2,x3),.. at ``positions`` [n] by ``freqs`` [r / 2],
    float32 angles."""
    r = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [n, r/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _plain(y, w):
    """A projection kept a plain [n, d] x [d, columns] product
    (models/mimo_v2.py, ``_qkv``)."""
    return jax.lax.optimization_barrier(y @ w)


def _project_latent(cfg, lp, x, positions):
    """One layer's latent attention inputs for rows x [n, d] at
    ``positions`` [n]: the absorbed query qc [n, H, lanes] (``q_nope
    W_uk^T | q_rope``, zero past ``latent_dim``), the latent lat [n,
    lanes] (``RMSNorm(c_kv) | k_r``, zero past it); and, for a family
    that projects more from them, the normed input h [n, d] and the
    query's latent cq [n, q_lora_rank]."""
    eps, n = cfg.rms_norm_eps, x.shape[0]
    H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    h = _rms(x, lp["input_ln"], eps)
    plain = lambda w, y=h: _plain(y, w)
    cq = _rms(plain(lp["w_dq"]), lp["q_ln"], eps)
    q = plain(lp["w_uq"], cq).reshape(n, H, -1)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = _rope_pairs(q[..., cfg.qk_nope_head_dim:], positions,
                         cfg.inv_freq())
    q_abs = jnp.einsum("nhk,hkr->nhr", q_nope, lp["w_uk"])
    widen = lambda t: jnp.pad(t, ((0, 0),) * (t.ndim - 1)
                              + ((0, cfg.latent_lanes - cfg.latent_dim),))
    qc = widen(jnp.concatenate([q_abs.astype(x.dtype), q_rope], axis=-1))
    ckv = plain(lp["w_dkv"])
    lat = widen(jnp.concatenate(
        [_rms(ckv[:, :rank], lp["kv_ln"], eps),
         _rope_pairs(ckv[:, rank:], positions, cfg.inv_freq())], axis=-1))
    return qc, lat, h, cq


def _project(cfg, lp, x, positions):
    """:func:`_project_latent`'s qc and lat, then the indexer's queries
    qi [n, Hi, di], key ki [n, di] and head weights wi [n, Hi]
    float32."""
    n, rope = x.shape[0], cfg.qk_rope_head_dim
    qc, lat, h, cq = _project_latent(cfg, lp, x, positions)
    plain = lambda w, y=h: _plain(y, w)

    def turn_first(t):
        return jnp.concatenate(
            [_rope_pairs(t[..., :rope], positions, cfg.inv_freq()),
             t[..., rope:]], axis=-1)

    qi = turn_first(plain(lp["w_qi"], cq).reshape(n, cfg.index_n_heads, -1))
    ki = turn_first(_layer_norm(plain(lp["w_ki"]), lp["ki_ln_g"],
                                lp["ki_ln_b"], 1e-6))
    wi = plain(lp["w_wi"]).astype(jnp.float32) * (
        cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return qc, lat, qi, ki, wi


def _index_scores(qi, wi, keys):
    """I[t, u] = sum_j w[t,j] relu(qI[t,j] . kI[u]), float32: qi
    [.., Hi, di], wi [.., Hi], keys [.., u, di] -> [.., u] (``..`` the
    same leading axes, or none on the keys)."""
    s = jnp.einsum("nhd,ud->nhu" if keys.ndim == 2 else "nhd,nud->nhu",
                   qi, keys, preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * wi[..., None]).sum(axis=-2)


def _out_proj(cfg, lp, o_lat):
    """o_lat [n, H, rank] float32, the probabilities' sum of latents a
    head -> the layer's attention output [n, d]."""
    o = jnp.einsum("nhr,hrv->nhv", o_lat.astype(lp["w_uv"].dtype),
                   lp["w_uv"])
    return o.reshape(o.shape[0], -1) @ lp["wo"]


def _sparse_attend(cfg, qc, sel, ok):
    """qc [n, H, lanes] against each row's own chosen latents sel
    [n, k, lanes] where ``ok`` [n, k]: the probabilities' sum of the
    latents' first ``kv_lora_rank`` values, float32 [n, H, rank]."""
    scale = cfg.logit_divisor
    s = jnp.einsum("nhc,nkc->nhk", qc, sel,
                   preferred_element_type=jnp.float32) / scale
    s = jnp.where(ok[:, None, :], s, _NEG)
    p = jnp.where(ok[:, None, :],
                  jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("nhk,nkr->nhr", p.astype(sel.dtype),
                      sel[..., :cfg.kv_lora_rank],
                      preferred_element_type=jnp.float32)


def _ffn(cfg, w, lp, f_kind, f, x, rows, counts):
    """x + ffn(rms(x)); an expert layer adds the shared expert, computed
    whole, to the held experts' part of the routed sum (``moe_held_ffn``:
    ``rows`` [n] marks real tokens, ``counts`` int32 gains)."""
    y = _rms(x, lp["post_ln"], cfg.rms_norm_eps)
    if f_kind == "dense":
        return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) \
            @ lp["w_down"], counts
    with jax.named_scope("moe_shared_ffn"):
        shared = (jax.nn.silu(y @ lp["ws_gate"]) * (y @ lp["ws_up"])) \
            @ lp["ws_down"]
    out, counts = moe_held_ffn(
        y, lp["router"], lp["router_bias"], [w[n] for n in _EXPERTS], f,
        rows, counts, top_k=cfg.num_experts_per_tok, held=cfg.held_experts,
        scoring=cfg.scoring_func, n_group=cfg.n_group,
        topk_group=cfg.topk_group, gate_scale=cfg.routed_scaling_factor)
    return x + shared + out.astype(x.dtype), counts


def _logits(cfg, x, final_norm, lm_head):
    x = _rms(x, final_norm, cfg.rms_norm_eps)
    return (x @ lm_head).astype(jnp.float32)


def _key_chunk(n_blocks, block, at_most):
    """Keys a piece of a prefill block's loops over the row's earlier
    tokens takes: whole blocks, as many as divide the window and hold at
    most ``at_most`` keys."""
    return block * max(m for m in range(1, n_blocks + 1)
                       if n_blocks % m == 0 and (m == 1
                                                 or m * block <= at_most))


def _sortable(scores):
    """float32 scores as uint32 keys of the same order (-inf lowest)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _chosen_mask(scores, k):
    """[n, t] bool: each row's ``k`` largest scores, of equal scores the
    earliest (what ``lax.top_k`` keeps), found without a sort: the
    ``k``-th largest key bit by bit, 32 counting passes over the scores,
    then the ties at that key in order (a pass of its own, taken only
    when some row has more of them than it has room for)."""
    u = _sortable(scores)

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where((u >= cand[:, None]).sum(axis=1) >= k, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((u.shape[0],), jnp.uint32))[:, None]
    above, tie = u > kth, u == kth
    room = k - above.sum(axis=1, keepdims=True)
    return jax.lax.cond(
        (tie.sum(axis=1, keepdims=True) > room).any(),
        lambda: above | (tie & (jnp.cumsum(tie, axis=1) <= room)),
        lambda: above | tie)


def _row_chosen(scores, pos, k):
    """[1, w] bool: the ``k`` largest of ONE row's scores [1, w] over
    columns ``0..pos`` ([1]: its last token's), of equal scores the
    earliest: on the chip one launch of a kernel that keeps the row in
    fast memory for all its passes, elsewhere ``_chosen_mask``, its
    oracle."""
    if topk_mask.kernel_serves():
        return topk_mask.chosen_mask_pallas(scores, pos, k)
    seen = jnp.arange(scores.shape[1])[None, :] <= pos[:, None]
    return seen & _chosen_mask(jnp.where(seen, scores, -jnp.inf), k)


def _mask_columns(mask, k):
    """mask [t] bool with at most ``k`` set -> (their columns in order
    [k] int32, which of the ``k`` stand for one [k] bool), without a
    sort or a scatter: by counts over pieces of 128 columns, the piece
    an entry falls in, then its place within that piece."""
    piece = _LANES
    m = jnp.pad(mask, (0, -mask.shape[0] % piece)).reshape(-1, piece)
    count = m.sum(axis=1, dtype=jnp.int32)
    start = jnp.cumsum(count) - count
    nth = jnp.arange(k, dtype=jnp.int32)
    at = (start[None, :] <= nth[:, None]).sum(axis=1) - 1
    hot = at[:, None] == jnp.arange(m.shape[0])[None, :]
    # an entry's piece of the mask and the running count along it, by
    # two products: 0 / 1 and counts to 128 are exact
    ones = lambda t: t.astype(jnp.bfloat16)
    own = jnp.dot(ones(hot), ones(m), preferred_element_type=jnp.float32)
    upto = jnp.dot(ones(own), ones(jnp.triu(jnp.ones((piece, piece), bool))),
                   preferred_element_type=jnp.float32)
    rank = (nth - (hot * start[None, :]).sum(axis=1)).astype(jnp.float32)
    within = (upto <= rank[:, None]).sum(axis=1)
    return at * piece + jnp.minimum(within, piece - 1), nth < count.sum()


def _block_pieces(total, blk, start, first, at_most):
    """(keys a piece, first piece, one past the last) of a loop over a
    prefilled row's tokens from its first to the block's own."""
    kc = _key_chunk(total // blk, blk, at_most)
    return kc, first * blk // kc, (start + blk - 1) // kc + 1


def _block_seen(qcol, pad, at, n):
    """[blk, n]: the keys at columns ``at..`` the queries at columns
    ``qcol`` may see (column ``pad`` holds position 0)."""
    kcol = at + jnp.arange(n)
    return (kcol[None, :] <= qcol[:, None]) & (kcol[None, :] >= pad)


def _causal_latent_pass(cfg, qc, lat_c, l, start, qcol, pad, first,
                        allowed=None, at_most=None):
    """The absorbed queries qc [blk, H, lanes] of one block of a cold
    prefill (columns ``qcol`` = ``start..`` of the window) against the row's
    latents lat_c [L, total, lanes] of layer ``l`` from the row's first
    token to the block's own; ``allowed`` [blk, total] narrows what a
    query sees. On the chip ONE launch of a kernel that keeps scores,
    probabilities and the running sum in fast memory
    (``kernels/latent_attention.py``, ``mla_latent_prefill``); elsewhere,
    and as that kernel's oracle, an online softmax a piece of at most
    ``at_most`` keys at a time (``ATTEND_KEYS``). Returns the
    probabilities' sum of latents [blk, H, rank] float32."""
    blk, H = qc.shape[0], qc.shape[1]
    total, lanes = lat_c.shape[1:]
    rank = cfg.kv_lora_rank
    scale = cfg.logit_divisor
    if prefill_kernel_serves(qc, lat_c, rank):
        with jax.named_scope("mla_prefill_attn"):
            return latent_prefill_pallas(qc, lat_c, l, start, pad, allowed,
                                         rank=rank, scale=1.0 / scale)
    kc, lo, hi = _block_pieces(total, blk, start, first,
                               at_most or ATTEND_KEYS)

    def fold(j, state):
        m, den, acc = state
        at = j * kc
        keys = jax.lax.dynamic_slice(lat_c, (l, at, 0),
                                     (1, kc, lanes))[0]
        ok = _block_seen(qcol, pad, at, kc)
        if allowed is not None:
            ok &= jax.lax.dynamic_slice(allowed, (0, at), (blk, kc))
        s = jnp.einsum("nhc,uc->hnu", qc, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.where(ok[None], s / scale, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("hnu,ur->hnr", p.astype(keys.dtype),
                        keys[:, :rank],
                        preferred_element_type=jnp.float32)
        return (m_new, alpha * den + p.sum(axis=-1),
                alpha[..., None] * acc + pv)

    with jax.named_scope("mla_prefill_attn"):
        _, den, acc = jax.lax.fori_loop(
            lo, hi, fold,
            (jnp.full((H, blk), _NEG, jnp.float32),
             jnp.zeros((H, blk), jnp.float32),
             jnp.zeros((H, blk, rank), jnp.float32)))
        return jnp.swapaxes(
            acc / jnp.maximum(den, 1e-30)[..., None], 0, 1)


def _block_attention(cfg, lp, qc, qi, wi, lat_c, ki_c, l, start, pad, first):
    """One layer's attention for one block of a cold prefill: queries at
    columns ``start..`` of the window (column ``pad`` holds position 0)
    against the row's latents lat_c [L, total, lanes] and indexer keys
    ki_c [L, total, di] of layer ``l``, the block's own among them.
    Two ways, by where the block's last row lies. Under ``index_topk``
    every earlier token is allowed: nothing is scored, the pass is
    causal over the row so far. From there on the indexer scores the row
    so far, each query keeps its ``index_topk`` best (a query with fewer
    earlier tokens keeps them all), and the kept set is a MASK over the
    causal pass, whose work still follows the context: on a v5e the
    compiler's gather of a query's chosen latents costs more than the
    pass over the whole row under 98 k of context, past every window
    the engine serves (PERF.md, Findings PR 42). Returns the
    probabilities' sum of latents [blk, H, rank] float32."""
    blk, total = qc.shape[0], lat_c.shape[1]
    k_top = min(cfg.index_topk, total)
    qcol = start + jnp.arange(blk)
    seen = functools.partial(_block_seen, qcol, pad)
    causal = functools.partial(_causal_latent_pass, cfg, qc, lat_c, l, start,
                               qcol, pad, first)

    def scores():
        """The indexer's scores of the block's queries against the row
        so far, [blk, total] float32, a piece of keys at a time; -inf
        where a query may not look."""
        kc, lo, hi = _block_pieces(total, blk, start, first, SCORE_KEYS)

        def piece(j, buf):
            keys = jax.lax.dynamic_slice(
                ki_c, (l, j * kc, 0), (1, kc, ki_c.shape[-1]))[0]
            return jax.lax.dynamic_update_slice(
                buf, _index_scores(qi, wi, keys), (0, j * kc))

        with jax.named_scope("dsa_index_scores"):
            buf = jax.lax.fori_loop(
                lo, hi, piece, jnp.full((blk, total), -jnp.inf, jnp.float32))
            return jnp.where(seen(0, total), buf, -jnp.inf)

    def masked():
        sc = scores()
        with jax.named_scope("dsa_topk"):
            allowed = _chosen_mask(sc, k_top)
        return causal(allowed)

    last = start + blk - 1 - pad            # the last row's position
    return jax.lax.cond(last >= cfg.index_topk, masked, causal)


def _prefill_attend(cfg, lp, x, positions, caches, l, start, pad, first):
    """One layer's attention for one block of this family's cold prefill
    (:func:`_prefill`'s ``attend``): the block's latents and indexer keys
    go into the row's carried caches, then :func:`_block_attention`."""
    lat_c, ki_c = caches
    qc, lat, qi, ki, wi = _project(cfg, lp, x, positions)
    lat_c = jax.lax.dynamic_update_slice(lat_c, lat[None], (l, start, 0))
    ki_c = jax.lax.dynamic_update_slice(ki_c, ki[None], (l, start, 0))
    o_lat = _block_attention(cfg, lp, qc, qi, wi, lat_c, ki_c, l, start, pad,
                             first)
    return o_lat, (lat_c, ki_c)


def _prefill(cfg, w, embed, final_norm, lm_head, ids, pad_len, table_row,
             pool, block, attend=_prefill_attend):
    """The cold prefill of ONE right-aligned row (ids [1, s], pad_len
    [1]); ``paged_stack.walk_blocks`` has the walk. What the row caches a
    token and layer (this family: latents and indexer keys; one carried
    array a page pool, as wide as its pages) lies in contiguous carries,
    which a block reads from the row's first token to its own rows:
    ``attend(cfg, lp, x, positions, caches, l, start, pad, first)`` writes
    the block's own and gives the probabilities' sum of latents [block, H,
    rank] and the carries. At the window's end they are written, whole
    pages through ``table_row``, every pool under the one table."""
    *pages, counts = pool
    window = block_window(ids, pad_len, block)
    pad, total = window.pad, window.total

    def run_layers(x, state, blk):
        def layer(f_kind, l0, carry, j):
            x, caches, counts = carry
            l = l0 + j
            lp = _layer_params(w, f_kind, l, j)
            o_lat, caches = attend(cfg, lp, x, blk.positions, caches, l,
                                   blk.start, pad, blk.first)
            x, counts = _ffn(cfg, w, lp, f_kind, j,
                             x + _out_proj(cfg, lp, o_lat), blk.rows, counts)
            return (x, caches, counts), None

        x, *state = scan_runs(cfg.runs(), layer, (x, *state))
        return x, tuple(state)

    (caches, counts), last = walk_blocks(
        window, embed,
        lambda: (tuple(jnp.zeros((cfg.num_hidden_layers, total, p.shape[-1]),
                                 embed.dtype) for p in pages), counts),
        run_layers)
    logits = _logits(cfg, last, final_norm, lm_head)
    mb, bs = table_row.shape[0], pages[0].shape[-2]
    pages = [p.at[:, table_row].set(_row_pages(c[:, :, None], pad, mb, bs))
             for p, c in zip(pages, caches)]
    return logits, (*pages, counts)


def decode_widths(s, index_topk, block):
    """The column counts a decode step may score one row over, ascending:
    four times ``index_topk`` in whole pages, doubled while that stays
    under the table's ``s`` columns, then ``s`` itself."""
    w = -(-4 * index_topk // block) * block
    out = []
    while w < s:
        out.append(w)
        w *= 2
    return (*out, s)


def _width_index(pos, widths):
    """pos [..] (a row's new token's position, NumPy on the host or
    traced in the program): which of ``widths`` serves the row, the least
    that holds columns ``0..pos``."""
    return (pos[..., None] >= np.asarray(widths)).sum(axis=-1)


def _live_rows(live):
    """live [b] bool -> (the slots with the live ones first, in order;
    how many are live): what the walk over a step's rows visits."""
    return jnp.argsort(~live, stable=True), live.sum()


def _decode_attention(cfg, lp, x, l, kp, vp, tables, lens, rows):
    """One layer's attention for one token per slot at position ``lens``
    [b]: the token's latent and indexer key go into the row's pages at
    ``[layer, page]``. Then the live rows are walked one by one (``rows``
    = ``_live_rows``: a slot without a row is not visited and adds
    nothing), and what a row costs follows its context: over the least
    of ``decode_widths`` that covers it, its indexer pages are taken
    through the table and scored, the ``index_topk`` best kept as a mask
    (``_row_chosen``: no sort), and the absorbed query either passes
    once over the row's own latent pages under that mask or, from
    ``GATHER_FROM`` columns on, reads the chosen latents alone, fetched
    by their columns (``_mask_columns``)."""
    b = x.shape[0]
    n_layers, n_pages, _, bs, lanes = kp.shape
    widths = decode_widths(tables.shape[1] * bs, cfg.index_topk, bs)
    qc, lat, qi, ki, wi = _project(cfg, lp, x, lens)
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    off = lens % bs
    kp = _token_insert(kp, l, page, off, lat[:, None])
    vp = _token_insert(vp, l, page, off, ki[:, None])

    def row_pages(pool, r, w):
        """Slot ``r``'s first ``w`` columns of this layer, [1, w, lanes]."""
        at = jax.lax.dynamic_slice(tables, (r, 0), (1, w // bs))[0]
        flat = pool.reshape(n_layers * n_pages, bs, pool.shape[-1])
        # a table names pages of the pool: no fill behind the gather
        return flat.at[l * n_pages + at].get(
            mode="promise_in_bounds").reshape(1, w, -1)

    def attend(w, r):
        one = lambda t: jax.lax.dynamic_slice_in_dim(t, r, 1)
        with jax.named_scope("dsa_index_scores"):
            sc = _index_scores(one(qi), one(wi), row_pages(vp, r, w))
        k_top = min(cfg.index_topk, w)
        with jax.named_scope("dsa_topk"):
            allowed = _row_chosen(sc, one(lens), k_top)
            if w >= GATHER_FROM:
                cols, real = _mask_columns(allowed[0], k_top)
        with jax.named_scope("mla_sparse_decode"):
            if w < GATHER_FROM:
                return _sparse_attend(cfg, one(qc), row_pages(kp, r, w),
                                      allowed)
            at = jnp.take(one(tables)[0], cols // bs)
            sel = jnp.take(kp.reshape(n_layers * n_pages * bs, lanes),
                           (l * n_pages + at) * bs + cols % bs, axis=0)
            return _sparse_attend(cfg, one(qc), sel[None], real[None])

    def visit(j, o_lat):
        r = rows[0][j]
        o = jax.lax.switch(_width_index(lens[r], widths),
                           [lambda r, w=w: attend(w, r) for w in widths], r)
        return jax.lax.dynamic_update_slice(o_lat, o, (r, 0, 0))

    with jax.named_scope("mla_sparse_decode"):
        o_lat = jax.lax.fori_loop(
            0, rows[1], visit,
            jnp.zeros((b, qc.shape[1], cfg.kv_lora_rank), jnp.float32))
    return _out_proj(cfg, lp, o_lat), kp, vp


def _decode_step(cfg, w, embed, final_norm, lm_head, tok, tables, lens,
                 pool, live):
    """One token per slot through the whole stack: tok [b] -> (float32
    logits [b, V], pool); pool = (latent pages, indexer pages,
    counters)."""
    x = jnp.take(embed, tok, axis=0)
    rows = _live_rows(live)
    # traced and lowered once for both kinds of layer run (its ways by
    # width are most of the program's text)
    attention = jax.jit(functools.partial(_decode_attention, cfg))

    def attend(lp, x, l, pages):
        o, *pages = attention({name: lp[name] for name in _ATTN}, x, l,
                              *pages, tables, lens, rows)
        return o, pages

    x, pool = _decode_layers(cfg, w, x, pool, live, attend)
    return _logits(cfg, x, final_norm, lm_head), pool


def _decode_layers(cfg, w, x, pool, live, attend):
    """One token per slot, x [b, d], through every layer: ``attend(lp,
    x, l, pages)`` -> (the layer's attention output [b, d], the page
    pools with the tokens' own written); pool = (*page pools, device
    counters). Returns (x, pool)."""
    def layer(f_kind, l0, carry, j):
        x, (*pages, counts) = carry
        lp = _layer_params(w, f_kind, l0 + j, j)
        o, pages = attend(lp, x, l0 + j, pages)
        x, counts = _ffn(cfg, w, lp, f_kind, j, x + o, live, counts)
        return (x, (*pages, counts)), None

    return scan_runs(cfg.runs(), layer, (x, tuple(pool)))


def leaf_shapes(cfg, indexer=True):
    """name -> (shape, kind of leaf) of every parameter (``indexer``:
    with the indexer's four, ``_INDEXER``). The router and
    its selection bias are float32 whatever the model's dtype: a near-tie
    between two experts' scores is settled in the precision the scores
    are stated in."""
    d, ff, fe = (cfg.hidden_size, cfg.intermediate_size,
                 cfg.moe_intermediate_size)
    H, qr, rank = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, hdv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                       cfg.v_head_dim)
    L, E = cfg.num_hidden_layers, cfg.n_routed_experts
    nd, ne = cfg.first_k_dense_replace, cfg.n_moe
    fs = fe * cfg.n_shared_experts
    held = ne * cfg.held_experts[1]
    if indexer:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        index = {"w_qi": ((L, qr, Hi * di), "matrix"),
                 "w_ki": ((L, d, di), "matrix"),
                 "ki_ln_g": ((L, di), "one"), "ki_ln_b": ((L, di), "zero"),
                 "w_wi": ((L, d, Hi), "matrix")}
    else:
        index = {}
    return {"embed_tokens": ((cfg.vocab_size, d), "matrix"),
            "input_ln": ((L, d), "one"), "post_ln": ((L, d), "one"),
            "w_dq": ((L, d, qr), "matrix"), "q_ln": ((L, qr), "one"),
            "w_uq": ((L, qr, H * (nope + rope)), "matrix"),
            "w_dkv": ((L, d, rank + rope), "matrix"),
            "kv_ln": ((L, rank), "one"),
            "w_uk": ((L, H, nope, rank), "matrix"),
            "w_uv": ((L, H, rank, hdv), "matrix"),
            "wo": ((L, H * hdv, d), "matrix"),
            **index,
            "w_gate": ((nd, d, ff), "matrix"),
            "w_up": ((nd, d, ff), "matrix"),
            "w_down": ((nd, ff, d), "matrix"),
            "router": ((ne, d, E), "router"),
            "router_bias": ((ne, E), "bias"),
            "ws_gate": ((ne, d, fs), "matrix"),
            "ws_up": ((ne, d, fs), "matrix"),
            "ws_down": ((ne, fs, d), "matrix"),
            "we_gate": ((held, d, fe), "matrix"),
            "we_up": ((held, d, fe), "matrix"),
            "we_down": ((held, fe, d), "matrix"),
            "final_norm": ((d,), "one"),
            "lm_head": ((d, cfg.vocab_size), "matrix")}


def dsa_tokens(layers, index_topk, widths):
    """What the engine counts on the host at every decode launch
    (``PagedPrograms.host_counters``), from the contexts of the live
    rows at each of the launch's steps (int64 [steps, rows], as
    ``engine_decode_ctx_tokens_total`` counts them): the tokens the
    indexer scores and the tokens the attention then reads, a row, layer
    and step; and, third, the columns the program's way scores for them
    (``widths``: the decode program's ``decode_widths``, by the rule the
    program itself takes a row's way by), so that scored tokens over
    scored columns is the share of the scored columns that held a
    token."""
    return {"dsa_scored_tokens": lambda ctx: layers * int(ctx.sum()),
            "dsa_selected_tokens":
                lambda ctx: layers * int(np.minimum(ctx, index_topk).sum()),
            "dsa_scored_columns":
                lambda ctx: layers * int(np.asarray(widths)[
                    _width_index(ctx, widths)].sum())}


class GlmMoeDsaForCausalLM(nn.Layer):
    """Stacked-parameter GLM-5: the attention's, the indexer's and the
    norms' leaves stacked over all layers, the dense SwiGLU, the routers
    and the shared experts over the layers of their kind, and the held
    experts of every expert layer in one stack ``[expert layers * held,
    ...]``."""

    config_class, presets, indexer = GlmMoeDsaConfig, GLM_MOE_DSA_PRESETS, True

    def __init__(self, config="debug"):
        super().__init__()
        if isinstance(config, str):
            config = self.config_class(**self.presets[config])
        self.config = cfg = config
        from ..nn import initializer as I
        inits = {"matrix": I.Normal(0.0, 0.02), "one": I.Constant(1.0),
                 "zero": I.Constant(0.0), "router": I.Normal(0.0, 0.1),
                 "bias": I.Uniform(-0.05, 0.05)}
        for name, (shape, how) in leaf_shapes(cfg, self.indexer).items():
            p = self.create_parameter(shape=list(shape),
                                      default_initializer=inits[how])
            if cfg.dtype != "float32" and how in ("matrix", "one", "zero"):
                p._in_place_update(p._value.astype(cfg.dtype))
            self.add_parameter(name, p)

    def _stacked_names(self):
        return [*(n for n in _ATTN if self.indexer or n not in _INDEXER),
                *_FFN["dense"], *_FFN["moe"], *_EXPERTS]

    def forward(self, input_ids):
        raise NotImplementedError(
            f"{type(self).__name__} is served through DecodeEngine "
            "(paged_programs); it has no cache-free forward")

    def paged_programs(self, chunk, prefill_block, mp_axis=None,
                       seq_axis=None, n_seq=1):
        """What ``DecodeEngine`` binds for this family."""
        cfg = self.config
        # the decode program's widths follow from its table's length,
        # which it learns when it is traced: ahead of the first launch,
        # and the host counts after one
        widths = []

        def prefill_paged(stacked, embed, fnorm, lm, scales, ids, pad_len,
                          table_row, slot, *pool):
            """ids [1, s_max] right-aligned; the row's latents and
            indexer keys go into its pages inside the program (``slot``
            names no state: the family keeps none)."""
            logits, pool = _prefill(cfg, stacked, embed, fnorm, lm, ids,
                                    pad_len, table_row, pool, prefill_block)
            return (jnp.argmax(logits, axis=-1), *pool)

        def decode_chunk_paged(stacked, embed, fnorm, lm, scales, tok,
                               tables, lens, *pool):
            """One chunk; a slot with ``lens == 0`` holds no row: its
            tokens are scored against nothing, routed to no expert and
            counted nowhere."""
            bs = pool[0].shape[-2]
            widths[:] = decode_widths(tables.shape[1] * bs, cfg.index_topk,
                                      bs)
            return greedy_chunk(_decode_step, (cfg, stacked, embed, fnorm, lm),
                                chunk, tok, tables, lens, pool)

        family = "a program of this family's own (latent and indexer pages)"
        return PagedPrograms(
            prefill_paged=prefill_paged,
            decode_chunk_paged=decode_chunk_paged,
            kv_layers=cfg.num_hidden_layers, kv_heads=1,
            head_dim=cfg.latent_lanes, v_head_dim=cfg.index_head_dim,
            slot_state=lambda slots: (
                jax.ShapeDtypeStruct((4,), jnp.int32),),
            device_counters=("moe_pairs", "moe_expert_visits",
                             "moe_full_stream", "moe_stream_rows"),
            host_counters=dsa_tokens(cfg.num_hidden_layers, cfg.index_topk,
                                     widths),
            trace_scopes=("dsa_index_scores", "dsa_topk", "mla_prefill_attn",
                          "mla_sparse_decode", "moe_shared_ffn",
                          "moe_expert_ffn"),
            unsupported={
                "prefix_cache": f"a prefix hit needs {family} that starts "
                                "behind the cached pages",
                "paged=False": "the latent and the indexer's keys are "
                               "pages of the pool",
                "chunked_prefill": f"a prompt's chunks need {family}",
                "spec_decode": f"verifying a draft needs {family}",
                "kv_dtype='int8'": "the pools hold a latent and an "
                                   "indexer key, not per-head keys and "
                                   "values with a scale a page",
                "mesh": "the latent is shared by all heads and the held "
                        "experts have no sharding rule"})

"""The plain reference of a ``deepseek_v3`` configuration (DeepSeek-V3)
over the share of the experts and of the vocabulary that the
configuration's file gives the chip: straightforward jax.numpy, float32,
matmul precision "highest". Keys and values are EXPANDED from the latent a
head (the un-absorbed form), every query reads every earlier token under
a dense float32 softmax; the experts are a dense sum over the held ones
with weight zero where an expert was not chosen, plus the shared expert.
No cache, no pages, no absorption, no kernel, no grouped product. It
imports nothing of the program and is handed nothing the program made:
weights come from the seed (lib/deepseek_weights.py), one layer at a
time, cast up from what is stored. Queries go in blocks and heads in
groups so that the scores of a 33 k sequence fit one chip, a sequence's
queries in four parts, each against the keys up to its own end, and the
LAST layer is computed at the compared positions alone (nothing reads
its other rows).

    x = embed[ids]
    each layer:  h = x + attn(rms(x, g1));  y = h + ffn(rms(h, g2))
    query:       c_q = rms(n W_dq, g_q); q = c_q W_uq as H x (nope | rope)
    latent:      [c_kv | k_r] = n W_dkv; c_kv = rms(c_kv, g_kv);
                 [k_nope | v] = c_kv W_ukv as H x (nope | v); k = k_nope | k_r,
                 k_r the same for every head
    rope:        interleaved pairs (x0,x1),(x2,x3),.. on q_rope and k_r;
                 d = rope width, f_i = theta^(-2i/d), g_i = f_i / factor,
                 low = floor(d ln(orig / (beta_fast 2 pi)) / (2 ln theta)),
                 high = ceil(d ln(orig / (beta_slow 2 pi)) / (2 ln theta)),
                 r_i = clip((i - low) / (high - low), 0, 1),
                 inv_freq_i = g_i r_i + f_i (1 - r_i); cos and sin times
                 m(mscale) / m(mscale_all_dim) = 1, m(a) = 0.1 a ln(factor) + 1
    attention:   s[t,u] = q[t] . k[u] x (nope + rope)^-0.5 x m(mscale_all_dim)^2
                 over every u <= t; softmax in float32; o = p v as H x v; W_o
    dense ffn:   W_d(silu(n W_g) * (n W_u))
    expert ffn:  s = sigmoid(n W_r) in float32 over all the router's experts;
                 s' = s + b; the experts lie in n_group groups of consecutive
                 ones, a group's score is the sum of its two largest s', the
                 topk_group best groups stay; chosen = top-k of s' among their
                 experts; w_e = s_e / sum over chosen of s, times
                 routed_scaling_factor; shared(n) + sum over the chosen
                 experts HELD HERE of w_e W_d,e(silu(n W_g,e) * (n W_u,e));
                 what the absent experts would add is left out
    logits = rms(y, g_f) W_head, untied, over the held slice of the vocabulary

Two controls of "How correct is decided" (``served_gaps(...,
control=True)``; which one, the tools say by
``DEEPSEEK_REFERENCE_CONTROL``): ``int8`` (the default): every matrix that
multiplies activations rounded to int8 per output channel, the arithmetic
in bfloat16 at the default precision (the router stays float32);
``noscale``: float32 as the reference, but the softmax scale without
YaRN's ``m(mscale_all_dim)^2``, what a program that forgets it
computes."""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import deepseek_weights as W
from . import glm_reference as GR
from .reference import _gap_below_best, _matmul_precision, _pad_to, _rms

SEQ_BUCKET = 4096       # sequences are padded to a multiple of this
Q_BLOCK = 256           # queries whose attention scores are alive at a time
HEAD_GROUP = 8          # heads whose expanded keys and values are
T_BLOCK = 2048          # tokens a feed-forward pass takes at a time
PARTS = 4               # parts of a sequence's queries, each against the
#                         keys up to its own end


def inv_freq(cfg):
    """YaRN's blended frequencies, float32 [rope / 2]."""
    y, d, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    at = lambda turns: d * math.log(
        y["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(at(y["beta_fast"])), 0)
    high = min(math.ceil(at(y["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = 1.0 / theta ** (2 * i / d)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / y["factor"] * r + f * (1.0 - r)


def _rope_pairs(x, positions, freqs):
    """x [s, .., r], every dimension turned: interleaved pairs."""
    r = x.shape[-1]
    ang = positions[:, None].astype(jnp.float32) * freqs
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attend(cfg, lp, c_q, q_pos, c_kv, k_r, scale):
    """The queries at positions ``q_pos`` [q] (c_q [q, q_lora_rank])
    against the sequence's first keys (c_kv [keys, rank], k_r [keys,
    rope], already turned), each over every key at or before its own
    position: keys and values expanded a group of heads at a time, dense
    scores, a float32 softmax; [q, d]."""
    z = W.sizes(cfg)
    q_n, s = c_q.shape[0], c_kv.shape[0]
    h, nope, rope, hdv, rank = (z["h"], z["nope"], z["rope"], z["hdv"],
                                z["rank"])
    freqs = inv_freq(cfg)
    seen = jnp.arange(s)[None, :] <= q_pos[:, None]
    blk = min(Q_BLOCK, q_n)
    seen = seen.reshape(q_n // blk, blk, s)
    g = min(HEAD_GROUP, h)
    groups = lambda w, axis: jnp.moveaxis(
        w.reshape(*w.shape[:axis], h // g, g, *w.shape[axis + 1:]), axis, 0)
    w_uq = groups(lp["w_uq"].reshape(-1, h, nope + rope), 1)
    w_ukv = groups(lp["w_ukv"].reshape(rank, h, nope + hdv), 1)
    w_o = groups(lp["wo"].reshape(h, hdv, -1), 0)

    def group(acc, ws):
        wq, wkv, wo = ws
        q = jnp.einsum("sq,qgd->sgd", c_q, wq)
        q = jnp.concatenate(
            [q[..., :nope], _rope_pairs(q[..., nope:], q_pos, freqs)],
            axis=-1)
        kv = jnp.einsum("sr,rgd->sgd", c_kv, wkv)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r[:, None, :], (s, g, rope))], axis=-1)
        v = kv[..., nope:]

        def block(xs):
            qb, ok = xs                                 # [Q, g, hd], [Q, s]
            scores = jnp.einsum("sgd,tgd->gst", qb, k).astype(jnp.float32)
            scores = jnp.where(ok[None], scores * scale, -jnp.inf)
            p = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
            return jnp.einsum("gst,tgd->sgd", p, v)

        o = jax.lax.map(block, (q.reshape(q_n // blk, blk, g, -1), seen))
        return acc + jnp.einsum("sgv,gvd->sd", o.reshape(q_n, g, hdv),
                                wo), None

    out, _ = jax.lax.scan(
        group, jnp.zeros((q_n, lp["wo"].shape[-1]), c_q.dtype),
        (w_uq, w_ukv, w_o))
    return out


def route(cfg, router, bias, n):
    """[s, experts] float32: an expert's weight for each token, zero
    where the token did not choose it."""
    z = W.sizes(cfg)
    scores = jax.nn.sigmoid(jnp.dot(n.astype(jnp.float32), router,
                                    precision="highest"))
    choice = scores + bias
    per = z["experts"] // z["n_group"]
    if z["n_group"] > 1:
        by_group = choice.reshape(-1, z["n_group"], per)
        group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)
        _, kept = jax.lax.top_k(group_score, z["topk_group"])
        stays = jax.nn.one_hot(kept, z["n_group"], dtype=jnp.float32).sum(1)
        choice = jnp.where(jnp.repeat(stays > 0, per, axis=1), choice,
                           -jnp.inf)
    _, chosen = jax.lax.top_k(choice, z["top_k"])
    picked = jax.nn.one_hot(chosen, z["experts"], dtype=jnp.float32).sum(1)
    weights = scores * picked
    weights = weights / weights.sum(-1, keepdims=True)
    return weights * cfg["routed_scaling_factor"]


def _experts(cfg, stored, lp, n, precision):
    """The shared expert plus the held experts' part: every held expert
    over every token, its weight zero where it was not chosen. The
    experts are cast up one at a time."""
    z = W.sizes(cfg)
    weights = jax.lax.dynamic_slice_in_dim(
        route(cfg, lp["router"], lp["router_bias"], n), z["first"],
        z["held"], axis=1)

    def one(acc, xs):
        e = GR._cast(dict(zip(("we_gate", "we_up", "we_down"), xs[:3])),
                     precision)
        y = GR._swiglu(n, e["we_gate"], e["we_up"], e["we_down"])
        return acc + xs[3][:, None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        one, GR._swiglu(n, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
        (stored["we_gate"], stored["we_up"], stored["we_down"], weights.T))
    return out


def _ffn(cfg, stored, lp, h, kind, precision):
    """h + ffn(rms(h)), ``T_BLOCK`` tokens at a time."""
    def some(hb):
        n = _rms(hb, lp["post_ln"], cfg["rms_norm_eps"])
        if kind == "dense":
            return hb + GR._swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return hb + _experts(cfg, stored, lp, n, precision)

    t = min(T_BLOCK, h.shape[0])
    return jax.lax.map(some, h.reshape(-1, t, h.shape[1])).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "precision",
                                             "yarn_scale"))
def _layer_step(stored, x, at, cfg_items, kind, precision, yarn_scale):
    """One layer over the whole sequence x [s, d]; with ``at`` [q] (the
    last layer) its output at those rows alone."""
    cfg = W.cfg_of(cfg_items)
    z, eps = W.sizes(cfg), cfg["rms_norm_eps"]
    scale = z["scale"] if yarn_scale \
        else (z["nope"] + z["rope"]) ** -0.5
    with jax.default_matmul_precision(_matmul_precision(precision)):
        lp = GR._cast({k: v for k, v in stored.items()
                       if not k.startswith("we_")}, precision)
        s = x.shape[0]
        n = _rms(x, lp["input_ln"], eps)
        ckv = n @ lp["w_dkv"]
        c_kv = _rms(ckv[:, :z["rank"]], lp["kv_ln"], eps)
        k_r = _rope_pairs(ckv[:, z["rank"]:], jnp.arange(s), inv_freq(cfg))
        c_q = lambda rows: _rms(rows @ lp["w_dq"], lp["q_ln"], eps)
        if at is not None:
            h = x[at] + _attend(cfg, lp, c_q(n[at]), at, c_kv, k_r, scale)
            return _ffn(cfg, stored, lp, h, kind, precision)
        parts = PARTS if s % (PARTS * Q_BLOCK) == 0 else 1
        out = []
        for p in range(parts):
            a, b = p * s // parts, (p + 1) * s // parts
            out.append(_attend(cfg, lp, c_q(n[a:b]), jnp.arange(a, b),
                               c_kv[:b], k_r[:b], scale))
        return _ffn(cfg, stored, lp, x + jnp.concatenate(out), kind,
                    precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _stored_layer(key, layer, cfg_items, kind):
    return W.make_layer(key, W.cfg_of(cfg_items), layer, kind, jnp.bfloat16)


def logits_of(seed, cfg, tokens, positions, precision="float32",
              yarn_scale=True):
    """Logits [len(positions), vocab] at ``positions`` of one sequence
    ``tokens`` [s], by a full forward pass, layer by layer, each layer's
    leaves made from the seed when its turn comes; the last layer and
    the head at ``positions`` alone. Everything is causal, so the zeros
    the sequence is padded with change nothing at or before its last
    real token."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    bucket = int(np.lcm(np.lcm(SEQ_BUCKET, Q_BLOCK), T_BLOCK))
    tokens = _pad_to(np.asarray(tokens, np.int32), bucket)
    n = len(positions)
    positions = _pad_to(np.asarray(positions, np.int32),
                        Q_BLOCK if n <= T_BLOCK else T_BLOCK)
    x = GR._embed(key, jnp.asarray(tokens), items, precision)
    kinds = W.kinds(cfg)
    for layer, kind in enumerate(kinds):
        at = jnp.asarray(positions) if layer == len(kinds) - 1 else None
        x = _layer_step(_stored_layer(key, layer, items, kind), x, at, items,
                        kind, precision, yarn_scale)
    return GR._head(key, x, jnp.arange(len(positions)), items, precision)[:n]


def served_gaps(seed, cfg, sequence, n_prompt, control=False):
    """For one finished request (``sequence`` = prompt + served tokens):
    how far each served token's float32 reference logit lies below the
    reference's best at that position. With ``control`` also the same
    for the token the control puts first at each position."""
    sequence = np.asarray(sequence, np.int32)
    positions = np.arange(n_prompt - 1, sequence.size - 1)
    ref = logits_of(seed, cfg, sequence[:-1], positions)
    out = {"served": _gap_below_best(ref, sequence[n_prompt:])}
    if control:
        how = os.environ.get("DEEPSEEK_REFERENCE_CONTROL", "int8")
        kw = {"yarn_scale": False} if how == "noscale" \
            else {"precision": how}
        low = logits_of(seed, cfg, sequence[:-1], positions, **kw)
        out["control"] = _gap_below_best(ref, np.asarray(jnp.argmax(low, -1)))
    return out

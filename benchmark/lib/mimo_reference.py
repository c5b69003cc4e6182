"""The plain reference of a ``mimo_v2`` configuration (MiMo-V2-Flash,
MiMo-V2.5) over the share of the experts and of the vocabulary that the
configuration's file gives the chip: straightforward jax.numpy, float32,
matmul precision "highest", a full causal or banded mask (queries in
blocks, so that the scores of 9216 tokens fit), a dense sum over the held
experts with weight zero where an expert was not chosen. No ring, no
pages, no kernels, no grouped product. It imports nothing of the program
and is handed nothing the program made: weights come from the seed
(lib/mimo_weights.py), one layer at a time, cast up from what is stored.

    x = embed[ids]
    each layer:  h = x + attn(rms(x, g1));  y = h + ffn(rms(h, g2))
    attention:   q = n W_q as H x hd, k = n W_k as Hkv x hd,
                 v = attention_value_scale * n W_v as Hkv x hdv; no biases;
                 rope (half-rotation) on the first int(hd * partial_rotary_factor)
                 dimensions of q and k, base rope_theta in a global layer
                 (pattern 0), swa_rope_theta in a window layer (pattern 1);
                 scores q k^T / sqrt(hd), causal;
                 pattern 0: Hkv = num_key_value_heads, every earlier key;
                 pattern 1: Hkv = swa_num_key_value_heads, keys t-window+1 .. t,
                 and a sink logit b_h a head: p[t,u] = exp(s[t,u]) /
                 (exp(b_h) + sum_u' exp(s[t,u'])); o = p v as H x hdv; W_o
    dense ffn:   W_d(silu(n W_g) * (n W_u))
    expert ffn:  s = sigmoid(n W_r) in float32 over all the router's experts;
                 chosen = top-k of s + b; w_e = s_e / sum over chosen of s
                 (norm_topk_prob), times routed_scaling_factor where given;
                 sum over the chosen experts HELD HERE of
                 w_e W_d,e(silu(n W_g,e) * (n W_u,e)); what the absent
                 experts would add is left out
    logits = rms(y, g_f) W_head, untied, over the held slice of the vocabulary

``precision="int8"`` is the control of "How correct is decided": every
matrix that multiplies activations rounded to int8 per output channel,
the arithmetic in bfloat16 at the default precision (the router and the
sink logits stay float32, as the configuration states them)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import mimo_weights as W
from .reference import (POS_BUCKET, _gap_below_best, _matmul_precision,
                        _pad_to, _rms)

SEQ_BUCKET = 1024       # sequences are padded to a multiple of this
Q_BLOCK = 256           # queries whose scores are alive at a time

FLOAT32 = ("router", "router_bias", "sink")


def _fake_int8(w):
    """Round [.., in, out] matrices to int8 per output channel and back."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _cast(leaves, precision):
    if precision == "float32":
        return {k: v.astype(jnp.float32) for k, v in leaves.items()}
    return {k: v if k in FLOAT32 else
            (_fake_int8(v) if v.ndim >= 2 and k != "embed_tokens"
             else v).astype(jnp.bfloat16) for k, v in leaves.items()}


def _rope_part(x, positions, theta, rot):
    """x [s, h, hd]: the first ``rot`` dimensions turned, their two
    halves a pair; the rest as they are."""
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rot].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rot:]], -1)


def _attention(cfg, lp, n, kind):
    z = W.sizes(cfg)
    s, hd, hdv, kvh = n.shape[0], z["hd"], z["hdv"], z["kv"][kind]
    theta = cfg["swa_rope_theta"] if kind == "window" else cfg["rope_theta"]
    positions = jnp.arange(s)
    q = _rope_part((n @ lp["wq"]).reshape(s, -1, hd), positions, theta,
                   z["rot"])
    k = _rope_part((n @ lp["wk"]).reshape(s, kvh, hd), positions, theta,
                   z["rot"])
    v = (n @ lp["wv"]).reshape(s, kvh, hdv) * jnp.asarray(
        cfg["attention_value_scale"], n.dtype)
    qg = q.reshape(s, kvh, -1, hd)
    keys = jnp.arange(s)

    def block(xs):
        qb, q0 = xs                                     # [Q, kvh, g, hd]
        scores = jnp.einsum("sngd,tnd->ngst", qb, k).astype(jnp.float32)
        scores = scores / np.sqrt(hd)
        at = (q0 + jnp.arange(qb.shape[0]))[:, None]
        seen = keys[None, :] <= at
        if kind == "window":
            seen &= keys[None, :] > at - z["window"]
        e = jnp.where(seen[None, None], jnp.exp(
            scores - jnp.max(jnp.where(seen[None, None], scores, -jnp.inf),
                             axis=-1, keepdims=True)), 0.0)
        total = e.sum(-1, keepdims=True)
        if kind == "window":
            top = jnp.max(jnp.where(seen[None, None], scores, -jnp.inf),
                          axis=-1, keepdims=True)
            sink = lp["sink"].astype(jnp.float32).reshape(kvh, -1, 1, 1)
            total = total + jnp.exp(sink - top)
        p = (e / total).astype(qb.dtype)
        return jnp.einsum("ngst,tnd->sngd", p, v)

    blocks = qg.reshape(s // Q_BLOCK, Q_BLOCK, *qg.shape[1:])
    out = jax.lax.map(block, (blocks, jnp.arange(s // Q_BLOCK) * Q_BLOCK))
    return out.reshape(s, -1) @ lp["wo"]


def route(cfg, lp, n):
    """[s, experts] float32: an expert's weight for each token, zero
    where the token did not choose it."""
    z = W.sizes(cfg)
    scores = jax.nn.sigmoid(jnp.dot(n.astype(jnp.float32), lp["router"],
                                    precision="highest"))
    _, chosen = jax.lax.top_k(scores + lp["router_bias"], z["top_k"])
    picked = jax.nn.one_hot(chosen, z["experts"], dtype=jnp.float32).sum(1)
    weights = scores * picked
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * (cfg.get("routed_scaling_factor") or 1.0)


def _experts(cfg, lp, n):
    """The held experts' part: every held expert over every token, its
    weight zero where it was not chosen."""
    z = W.sizes(cfg)
    weights = jax.lax.dynamic_slice_in_dim(route(cfg, lp, n), z["first"],
                                           z["held"], axis=1)

    def one(acc, xs):
        wg, wu, wd, w_e = xs
        y = (jax.nn.silu(n @ wg) * (n @ wu)) @ wd
        return acc + w_e[:, None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (lp["we_gate"], lp["we_up"], lp["we_down"],
                           weights.T))
    return out


def _layer(cfg, lp, x, kind):
    a_kind, f_kind = kind
    eps = cfg["layernorm_epsilon"]
    h = x + _attention(cfg, lp, _rms(x, lp["input_ln"], eps), a_kind)
    n = _rms(h, lp["post_ln"], eps)
    if f_kind == "dense":
        return h + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) \
            @ lp["w_down"]
    return h + _experts(cfg, lp, n)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "precision"))
def _layer_step(stored, x, cfg_items, kind, precision):
    with jax.default_matmul_precision(_matmul_precision(precision)):
        return _layer(W.cfg_of(cfg_items), _cast(stored, precision), x, kind)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _stored_layer(key, layer, cfg_items, kind):
    return W.make_layer(key, W.cfg_of(cfg_items), layer, kind, jnp.bfloat16)


_LAYERS = {}        # (seed, model) -> the stored leaves of each layer


def _layers_of(seed, cfg):
    """Every layer's leaves as they are stored, made from the seed one
    layer at a time and kept for the run's other sequences and for the
    control. One model at a time is kept."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    if (int(seed), items) not in _LAYERS:
        _LAYERS.clear()
        _LAYERS[int(seed), items] = [
            _stored_layer(key, layer, items, kind)
            for layer, kind in enumerate(W.kinds(cfg))]
    return _LAYERS[int(seed), items]


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _embed(key, tokens, cfg_items, precision):
    top = _cast(W.make_top(key, W.cfg_of(cfg_items), jnp.bfloat16,
                           only=("embed_tokens",)), precision)
    return jnp.take(top["embed_tokens"], tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(key, x, positions, cfg_items, precision):
    """float32 logits at ``positions``, over the held vocabulary."""
    cfg = W.cfg_of(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        top = _cast(W.make_top(key, cfg, jnp.bfloat16,
                               only=("final_norm", "lm_head")), precision)
        y = _rms(x[positions], top["final_norm"], cfg["layernorm_epsilon"])
        return (y @ top["lm_head"]).astype(jnp.float32)


def logits_of(seed, cfg, tokens, positions, precision="float32"):
    """Logits [len(positions), vocab] at ``positions`` of one sequence
    ``tokens`` [s], by a full forward pass, layer by layer. Everything is
    causal, so the zeros the sequence is padded with change nothing at
    or before its last real token."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    tokens = _pad_to(np.asarray(tokens, np.int32), SEQ_BUCKET)
    n = len(positions)
    positions = _pad_to(np.asarray(positions, np.int32), POS_BUCKET)
    x = _embed(key, jnp.asarray(tokens), items, precision)
    for stored, kind in zip(_layers_of(seed, cfg), W.kinds(cfg)):
        x = _layer_step(stored, x, items, kind, precision)
    return _head(key, x, jnp.asarray(positions), items, precision)[:n]


def served_gaps(seed, cfg, sequence, n_prompt, control=False):
    """For one finished request (``sequence`` = prompt + served tokens):
    how far each served token's float32 reference logit lies below the
    reference's best at that position. With ``control`` also the same
    for the token the int8 control puts first at each position."""
    sequence = np.asarray(sequence, np.int32)
    positions = np.arange(n_prompt - 1, sequence.size - 1)
    ref = logits_of(seed, cfg, sequence[:-1], positions)
    out = {"served": _gap_below_best(ref, sequence[n_prompt:])}
    if control:
        low = logits_of(seed, cfg, sequence[:-1], positions, precision="int8")
        out["control"] = _gap_below_best(ref, np.asarray(jnp.argmax(low, -1)))
    return out

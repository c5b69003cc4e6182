"""The plain reference of a ``granitemoehybrid`` configuration with no
routed experts (granite-4.0-h), after the published modelling code, whose
mixer is Bamba's Mamba-2: in straightforward jax.numpy, float32, matmul
precision "highest". The recurrence is a plain ``lax.scan`` over tokens:
no chunks, no cache, no kernels. It imports nothing of the program and is
handed nothing the program made: weights come from the seed
(lib/granite_weights.py), one layer at a time, cast up from what is
stored (the stored leaves are kept between the sequences of one run).

    x = embed[ids] * embedding_multiplier
    each layer:  x = x + residual_multiplier * mixer(rms(x, w_in))
                 x = x + residual_multiplier * W_down(silu(W_gate y) * W_up y),  y = rms(x, w_post)
    attention:   bias-free q, k, v; no positional embedding; causal
                 softmax(q k^T * attention_multiplier) v; W_o
    mamba-2:     [z, xBC, dt] = split(h W_in); xBC = silu(causal depthwise conv(xBC) + b)
                 [x, B, C] = split(xBC); dt = softplus(dt + dt_bias); a = exp(-dt * exp(A_log))
                 S_t = a S_{t-1} + dt x_t (x) B_t;  y_t = S_t C_t + D x_t
                 y = rms(y * silu(z), w_norm) over all of d_inner; y W_out
    logits = rms(x, w_f) embed^T / logits_scaling

``precision="int8"`` is the control of "How correct is decided": every
matrix that multiplies activations rounded to int8 per output channel,
the arithmetic in bfloat16 at the default precision (the state stays
float32, as the configuration states it)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import granite_weights as W
from .reference import (POS_BUCKET, _fake_int8, _gap_below_best,
                        _matmul_precision, _pad_to, _rms)

SEQ_BUCKET = 1024       # two shapes cover the cell's sequences (<= 1664)

MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_gate", "w_up",
            "w_down")
FLOAT32 = ("A_log", "dt_bias", "D")


def _cast(leaves, precision):
    if precision == "float32":
        return {k: v.astype(jnp.float32) for k, v in leaves.items()}
    return {k: v if k in FLOAT32 else
            (_fake_int8(v) if k in MATRICES else v).astype(jnp.bfloat16)
            for k, v in leaves.items()}


def _attention(cfg, lp, h):
    s, hd = h.shape[0], W.sizes(cfg)["ahd"]
    q = (h @ lp["wq"]).reshape(s, -1, hd)
    k = (h @ lp["wk"]).reshape(s, -1, hd)
    v = (h @ lp["wv"]).reshape(s, -1, hd)
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, -1, hd)
    scores = jnp.einsum("sngd,tnd->ngst", qg, k).astype(jnp.float32)
    scores = scores * cfg["attention_multiplier"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    return jnp.einsum("ngst,tnd->sngd", p, v).reshape(s, -1) @ lp["wo"]


def _mamba(cfg, lp, h):
    z_ = W.sizes(cfg)
    s, di, ds, k = h.shape[0], z_["di"], z_["ds"], z_["k"]
    proj = h @ lp["in_proj"]
    z, xbc, dt = (proj[:, :di], proj[:, di:di + z_["conv"]],
                  proj[:, di + z_["conv"]:])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype),
                              xbc]).astype(jnp.float32)
    conv = sum(padded[j:j + s] * lp["conv_w"][j].astype(jnp.float32)
               for j in range(k)) + lp["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(s, z_["nh"], z_["hd"])
    bm, cm = xbc[:, di:di + ds], xbc[:, di + ds:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(lp["A_log"]))

    def token(state, xs):
        a_t, dt_t, x_t, b_t, c_t = xs
        state = a_t[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, (state * c_t[None, None, :]).sum(-1)

    # unrolled: the same steps in the same order, eight to a trip of the
    # device's loop (a trip a token left the chip idle between steps: 0.4
    # s a layer where eight take 0.03; sixteen compile for 29 s a shape)
    _, y = jax.lax.scan(
        token, jnp.zeros((z_["nh"], z_["hd"], ds), jnp.float32),
        (a, dt, x, bm, cm), unroll=8)
    y = y + lp["D"][None, :, None] * x
    y = y.reshape(s, di) * jax.nn.silu(z.astype(jnp.float32))
    y = _rms(y, lp["ssm_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    return y.astype(h.dtype) @ lp["out_proj"]


def _layer(cfg, lp, x, kind):
    rm = cfg["residual_multiplier"]
    h = _rms(x, lp["input_ln"], cfg["rms_norm_eps"])
    mixed = _mamba(cfg, lp, h) if kind == "mamba" else _attention(cfg, lp, h)
    x = x + jnp.asarray(rm, x.dtype) * mixed
    y = _rms(x, lp["post_ln"], cfg["rms_norm_eps"])
    mlp = (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]
    return x + jnp.asarray(rm, x.dtype) * mlp


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "precision"))
def _layer_step(stored, x, cfg_items, kind, precision):
    with jax.default_matmul_precision(_matmul_precision(precision)):
        return _layer(dict(cfg_items), _cast(stored, precision), x, kind)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _stored_layer(key, layer, cfg_items, kind):
    return W.make_layer(key, dict(cfg_items), layer, kind, jnp.bfloat16)


_LAYERS = {}        # (seed, model) -> the stored leaves of each layer


def _layers_of(seed, cfg):
    """Every layer's leaves as they are stored (bfloat16 and the float32
    three), made from the seed one layer at a time and kept for the
    run's other sequences and for the control: a draw of 76 M values a
    layer and sequence was most of the reference's time. One model at a
    time is kept."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    if (int(seed), items) not in _LAYERS:
        _LAYERS.clear()
        _LAYERS[int(seed), items] = [
            _stored_layer(key, layer, items, kind)
            for layer, kind in enumerate(cfg["layer_types"])]
    return _LAYERS[int(seed), items]


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _embed(key, tokens, cfg_items, precision):
    cfg = dict(cfg_items)
    top = _cast(W.make_top(key, cfg, jnp.bfloat16, only=("embed_tokens",)),
                precision)
    x = jnp.take(top["embed_tokens"], tokens, axis=0)
    return x * jnp.asarray(cfg["embedding_multiplier"], x.dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(key, x, positions, cfg_items, precision):
    """float32 logits at ``positions``; the head is the embedding, tied."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        top = _cast(W.make_top(key, cfg, jnp.bfloat16), precision)
        lm = top["embed_tokens"].T
        if precision == "int8":
            lm = _fake_int8(lm).astype(jnp.bfloat16)
        y = _rms(x[positions], top["final_norm"], cfg["rms_norm_eps"])
        return (y @ lm).astype(jnp.float32) / cfg["logits_scaling"]


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
    for stored, kind in zip(_layers_of(seed, cfg), cfg["layer_types"]):
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

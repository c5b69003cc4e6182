"""The plain reference: the decoder as published (RMSNorm, rotary
embedding in the half-split convention, grouped-query attention with
the q/k/v biases where the configuration has them, SwiGLU), in
straightforward jax.numpy, float32, matmul precision "highest". No
kernels, no cache, no batching tricks; it imports nothing of the program
and is handed nothing the program made: weights come from the seed
(lib/weights.py), one layer at a time, cast up from the stored bfloat16.

``precision="int8"`` is the control of "How correct is decided": the same
mathematics with every weight matrix rounded to int8 per output channel
and the arithmetic in bfloat16 at the default precision, the nearest
step below what the configurations state (bfloat16) and the one the
program already offers (``quantize_weights_int8``, ``kv_dtype="int8"``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _rope(x, positions, theta):
    """x [s, h, hd]; rotates (x1, x2) = the two halves of each head."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs        # [s, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


Q_BLOCK = 1024          # queries per block once a sequence is longer than
LONG = 2048             # this: the scores of one block fit, those of all do not


def _attention_block(qg, k, v, q0):
    """Queries ``qg`` [sq, kvh, g, hd] at positions q0.. against all keys."""
    sq, hd = qg.shape[0], qg.shape[-1]
    scores = jnp.einsum("sngd,tnd->ngst", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    causal = (q0 + jnp.arange(sq))[:, None] >= jnp.arange(k.shape[0])[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(qg.dtype)
    return jnp.einsum("ngst,tnd->sngd", p, v)


def _attention(q, k, v):
    """Causal grouped attention on one sequence: q [s, h, hd], k and v
    [s, kvh, hd]. A long sequence is taken in blocks of queries, each
    recomputed in the backward pass, so that only one block's scores are
    alive at a time; the mathematics is the same."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, h // kvh, hd)
    if s <= LONG or s % Q_BLOCK:
        return _attention_block(qg, k, v, 0).reshape(s, h * hd)
    blocks = qg.reshape(s // Q_BLOCK, Q_BLOCK, kvh, h // kvh, hd)
    starts = jnp.arange(s // Q_BLOCK) * Q_BLOCK
    out = jax.lax.map(jax.checkpoint(
        lambda xs: _attention_block(xs[0], k, v, xs[1])), (blocks, starts))
    return out.reshape(s, h * hd)


def _fake_int8(w):
    """Round a [in, out] matrix to int8 per output channel and back."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _layer(cfg, lp, x, positions):
    hd = W.head_dim(cfg)
    s = x.shape[0]
    y = _rms(x, lp["input_ln"], cfg["rms_norm_eps"])
    q, k, v = y @ lp["wq"], y @ lp["wk"], y @ lp["wv"]
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _rope(q.reshape(s, -1, hd), positions, cfg["rope_theta"])
    k = _rope(k.reshape(s, -1, hd), positions, cfg["rope_theta"])
    x = x + _attention(q, k, v.reshape(s, -1, hd)) @ lp["wo"]
    y = _rms(x, lp["post_ln"], cfg["rms_norm_eps"])
    return x + (jax.nn.silu(y @ lp["w_gate"]) * (y @ lp["w_up"])) @ lp["w_down"]


def _cast(leaves, precision):
    if precision == "float32":
        return {k: v.astype(jnp.float32) for k, v in leaves.items()}
    if precision == "bfloat16":     # the configurations' own precision,
        return dict(leaves)         # for the test that sizes the control
    return {k: (_fake_int8(v) if v.ndim == 2 and k != "embed_tokens"
                else v).astype(jnp.bfloat16) for k, v in leaves.items()}


def _matmul_precision(precision):
    return "highest" if precision == "float32" else "default"


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer_step(key, layer, x, cfg_items, precision):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        lp = _cast(W.make_layer(key, cfg, layer, jnp.bfloat16), precision)
        return _layer(cfg, lp, x, jnp.arange(x.shape[0]))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _embed(key, tokens, cfg_items, precision):
    top = _cast(W._make_all(key, cfg_items, jnp.bfloat16,
                            only=("embed_tokens",)), precision)
    return jnp.take(top["embed_tokens"], tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(key, x, positions, cfg_items, precision):
    """float32 logits at ``positions``."""
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        only = ("final_norm", "embed_tokens") if cfg.get("tie_word_embeddings") \
            else ("final_norm", "lm_head")
        top = _cast(W._make_all(key, cfg_items, jnp.bfloat16, only=only),
                    precision)
        lm = top["lm_head"] if "lm_head" in top else top["embed_tokens"].T
        y = _rms(x[positions], top["final_norm"], cfg["rms_norm_eps"])
        return (y @ lm).astype(jnp.float32)


SEQ_BUCKET = 512        # sequences are padded to a multiple of this, so
POS_BUCKET = 128        # that the reference compiles a few shapes only


def _pad_to(a, multiple):
    extra = -a.size % multiple
    return np.concatenate([a, np.zeros(extra, a.dtype)])


def logits_of(seed, cfg, tokens, positions, precision="float32"):
    """Logits [len(positions), vocab] at ``positions`` of one sequence
    ``tokens`` [s], by a full forward pass, layer by layer. Attention is
    causal, so the zeros the sequence is padded with change nothing at
    or before its last real token."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    tokens = _pad_to(np.asarray(tokens, np.int32), SEQ_BUCKET)
    n = len(positions)
    positions = _pad_to(np.asarray(positions, np.int32), POS_BUCKET)
    x = _embed(key, jnp.asarray(tokens), items, precision)
    for layer in range(cfg["num_hidden_layers"]):
        x = _layer_step(key, layer, x, items, precision)
    return _head(key, x, jnp.asarray(positions), items, precision)[:n]


def _gap_below_best(ref, tokens):
    best = ref.max(axis=-1)
    return np.asarray(best - jnp.take_along_axis(
        ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0])


def first_choice_gaps(seed, cfg, tokens, precision):
    """At every position of ``tokens``: how far the token that
    ``precision`` puts first lies below the float32 reference's best."""
    positions = np.arange(len(tokens))
    ref = logits_of(seed, cfg, tokens, positions)
    low = logits_of(seed, cfg, tokens, positions, precision=precision)
    return _gap_below_best(ref, np.asarray(jnp.argmax(low, -1)))


def served_gaps(seed, cfg, sequence, n_prompt, control=False):
    """For one finished request (``sequence`` = prompt + served tokens):
    how far each served token's float32 reference logit lies below the
    reference's best at that position. With ``control`` also the same
    for the token the int8 control puts first at each position."""
    sequence = np.asarray(sequence, np.int32)
    # logits at position p predict token p + 1: positions n_prompt-1 ..
    # size-2 are the ones that produced the served tokens
    positions = np.arange(n_prompt - 1, sequence.size - 1)
    ref = logits_of(seed, cfg, sequence[:-1], positions)
    out = {"served": _gap_below_best(ref, sequence[n_prompt:])}
    if control:
        low = logits_of(seed, cfg, sequence[:-1], positions, precision="int8")
        out["control"] = _gap_below_best(ref, np.asarray(jnp.argmax(low, -1)))
    return out

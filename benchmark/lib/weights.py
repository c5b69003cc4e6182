"""Weights from the seed, made on the device in the type they are served
or trained in. Every leaf of layer ``l`` depends only on (seed, leaf,
l), so the reference can make one layer at a time and get bit for bit
what the program was given.

Shapes follow the configuration file's published keys (the names of the
model's public config.json). Names of the leaves are the stacked names
of paddle_tpu/models/llama.py, which is what the program is handed."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02
BIAS_STD = 0.05
NORM_STD = 0.05


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_leaves(cfg):
    """name -> (shape of one layer's leaf, kind)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    leaves = {
        "wq": ((d, h * hd), "matrix"), "wk": ((d, kvh * hd), "matrix"),
        "wv": ((d, kvh * hd), "matrix"), "wo": ((h * hd, d), "matrix"),
        "input_ln": ((d,), "norm"), "post_ln": ((d,), "norm"),
        "w_gate": ((d, ff), "matrix"), "w_up": ((d, ff), "matrix"),
        "w_down": ((ff, d), "matrix"),
    }
    if cfg.get("attention_bias"):
        leaves.update({"bq": ((h * hd,), "bias"), "bk": ((kvh * hd,), "bias"),
                       "bv": ((kvh * hd,), "bias")})
    return leaves


def top_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    leaves = {"embed_tokens": ((v, d), "matrix"), "final_norm": ((d,), "norm")}
    if not cfg.get("tie_word_embeddings"):
        leaves["lm_head"] = ((d, v), "matrix")
    return leaves


def seed_key(seed):
    """A key from any whole seed up to past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, shape, kind, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "matrix":
        return (MATRIX_STD * n).astype(dtype)
    if kind == "bias":
        return (BIAS_STD * n).astype(dtype)
    return (1.0 + NORM_STD * n).astype(dtype)


def _leaf_key(key, name, layer=None):
    k = jax.random.fold_in(key, sum(ord(c) * 131 ** i
                                    for i, c in enumerate(name)) % (2 ** 31))
    return k if layer is None else jax.random.fold_in(k, layer)


def make_layer(key, cfg, layer, dtype):
    """One layer's leaves (traced ``layer`` is fine)."""
    return {name: _draw(_leaf_key(key, name, layer), shape, kind, dtype)
            for name, (shape, kind) in layer_leaves(cfg).items()}


def make_top(key, cfg, dtype):
    return {name: _draw(_leaf_key(key, name), shape, kind, dtype)
            for name, (shape, kind) in top_leaves(cfg).items()}


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype", "only"))
def _make_all(key, cfg_items, dtype, only=None):
    cfg = dict(cfg_items)
    n_layers = cfg["num_hidden_layers"]
    out = make_top(key, cfg, dtype)
    per_layer = [make_layer(key, cfg, l, dtype) for l in range(n_layers)]
    for name in layer_leaves(cfg):
        out[name] = jnp.stack([lw[name] for lw in per_layer])
    if only is not None:
        out = {k: v for k, v in out.items() if k in only}
    return out


def model_items(cfg):
    """The hashable part of a configuration that fixes the model: its
    shapes and the constants of its equations."""
    keys = ("rope_theta", "rms_norm_eps", "hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "vocab_size",
            "head_dim", "attention_bias", "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


def make_all(seed, cfg, dtype=jnp.bfloat16):
    """Every leaf, stacked over layers, in one jitted call."""
    return _make_all(seed_key(seed), model_items(cfg), dtype)

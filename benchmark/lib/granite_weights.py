"""Weights of a ``granitemoehybrid`` configuration from the seed, made on
the device in the type they are served in. Every leaf of layer ``l``
depends only on (seed, leaf, l), so the reference makes one layer at a
time and gets bit for bit what the program was given.

Names are the stacked names of paddle_tpu/models/granite_hybrid.py: the
shared leaves (norms, SwiGLU) of every layer, the Mamba-2 leaves of a
``mamba`` layer, the attention leaves of an ``attention`` layer. What
drives the recurrence (``A_log``, ``dt_bias``, ``D``) is float32 and
drawn in the ranges Mamba-2's own initialisation uses (the
configuration's ``assumed``), so that the state carries over hundreds of
tokens: a state that forgot at once would hide a broken cache."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .weights import MATRIX_STD, NORM_STD, _leaf_key, seed_key  # noqa: F401

A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
CONV_RANGE = 0.5        # U(-1/sqrt(K), 1/sqrt(K)) at K = 4


def sizes(cfg):
    d = cfg["hidden_size"]
    nh, hd, ds = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di = cfg["mamba_expand"] * d
    return {"d": d, "ff": cfg["shared_intermediate_size"], "nh": nh, "hd": hd,
            "ds": ds, "di": di, "conv": di + 2 * cfg["mamba_n_groups"] * ds,
            "k": cfg["mamba_d_conv"], "h": cfg["num_attention_heads"],
            "kvh": cfg["num_key_value_heads"],
            "ahd": cfg.get("head_dim") or d // cfg["num_attention_heads"]}


def shared_leaves(cfg):
    """name -> (shape of one layer's leaf, kind of draw): what every
    layer has, the two norms and the shared SwiGLU."""
    z = sizes(cfg)
    d, ff = z["d"], z["ff"]
    return {"input_ln": ((d,), "norm"), "post_ln": ((d,), "norm"),
            "w_gate": ((d, ff), "matrix"), "w_up": ((d, ff), "matrix"),
            "w_down": ((ff, d), "matrix")}


def mixer_leaves(cfg, kind):
    """The leaves of a layer's mixer: ``mamba`` or ``attention``."""
    z = sizes(cfg)
    d = z["d"]
    if kind == "mamba":
        return {
            "in_proj": ((d, z["di"] + z["conv"] + z["nh"]), "matrix"),
            "conv_w": ((z["k"], z["conv"]), "conv"),
            "conv_b": ((z["conv"],), "conv"),
            "dt_bias": ((z["nh"],), "dt_bias"), "A_log": ((z["nh"],), "A_log"),
            "D": ((z["nh"],), "D"), "ssm_norm": ((z["di"],), "norm"),
            "out_proj": ((z["di"], d), "matrix")}
    return {"wq": ((d, z["h"] * z["ahd"]), "matrix"),
            "wk": ((d, z["kvh"] * z["ahd"]), "matrix"),
            "wv": ((d, z["kvh"] * z["ahd"]), "matrix"),
            "wo": ((z["h"] * z["ahd"], d), "matrix")}


def layer_leaves(cfg, kind):
    return {**shared_leaves(cfg), **mixer_leaves(cfg, kind)}


def top_leaves(cfg):
    """The embedding is drawn ``embedding_multiplier`` times smaller than
    a matrix: scaled, it enters the stack as an ordinary embedding does,
    and as the tied head it does not put the token just read first."""
    return {"embed_tokens": ((cfg["vocab_size"], cfg["hidden_size"]),
                             MATRIX_STD / cfg["embedding_multiplier"]),
            "final_norm": ((cfg["hidden_size"],), "norm")}


def _draw(key, shape, kind, dtype):
    if kind in ("matrix", "norm") or isinstance(kind, float):
        n = jax.random.normal(key, shape, jnp.float32)
        std = MATRIX_STD if kind == "matrix" else kind
        return (1.0 + NORM_STD * n if kind == "norm"
                else std * n).astype(dtype)
    u = jax.random.uniform(key, shape, jnp.float32)
    if kind == "conv":
        return (CONV_RANGE * (2.0 * u - 1.0)).astype(dtype)
    if kind == "A_log":         # A uniform in A_RANGE
        return jnp.log(A_RANGE[0] + u * (A_RANGE[1] - A_RANGE[0]))
    if kind == "dt_bias":       # softplus(dt_bias) log-uniform in DT_RANGE
        lo, hi = jnp.log(DT_RANGE[0]), jnp.log(DT_RANGE[1])
        dt = jnp.exp(lo + u * (hi - lo))
        return dt + jnp.log(-jnp.expm1(-dt))
    return 1.0 + NORM_STD * (2.0 * u - 1.0)     # D


def _make(key, leaves, layer, dtype):
    return {name: _draw(_leaf_key(key, name, layer), shape, how, dtype)
            for name, (shape, how) in leaves.items()}


def make_layer(key, cfg, layer, kind, dtype):
    """One layer's leaves (traced ``layer`` is fine; ``kind`` is not)."""
    return _make(key, layer_leaves(cfg, kind), layer, dtype)


def make_top(key, cfg, dtype, only=None):
    return {name: _draw(_leaf_key(key, name), shape, how, dtype)
            for name, (shape, how) in top_leaves(cfg).items()
            if only is None or name in only}


def model_items(cfg):
    """The hashable part of a configuration that fixes the model."""
    keys = ("hidden_size", "shared_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_n_groups",
            "embedding_multiplier", "residual_multiplier",
            "attention_multiplier", "logits_scaling", "rms_norm_eps")
    return tuple((k, cfg[k]) for k in keys if cfg.get(k) is not None) \
        + (("layer_types", tuple(cfg["layer_types"])),)


@functools.partial(jax.jit, static_argnames=("cfg_items", "which", "dtype"))
def stack_on_device(key, layers, cfg_items, which, dtype):
    """The leaves ``which`` (``shared``, ``mamba`` or ``attention``) of
    the layers ``layers`` [n], stacked: made a layer at a time by one
    loop on the device, each leaf the very draw :func:`make_layer` gives
    for that layer."""
    cfg = dict(cfg_items)
    leaves = shared_leaves(cfg) if which == "shared" \
        else mixer_leaves(cfg, which)
    return jax.lax.map(lambda l: _make(key, leaves, l, dtype), layers)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype", "only"))
def top_on_device(key, cfg_items, dtype, only=None):
    return make_top(key, dict(cfg_items), dtype, only)

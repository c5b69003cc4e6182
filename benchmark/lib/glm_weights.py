"""Weights of a ``glm_moe_dsa`` configuration (GLM-5) from the seed, made
on the device in the type they are served in. Every leaf of layer ``l``
depends only on (seed, leaf, l), and a routed expert's leaves on its id
among ALL the router's experts besides: every share of one model draws
the same expert 37, and the reference makes one layer at a time and gets
bit for bit what the program was given.

The file states the chip's share as ``mimo_v2``'s does:
``n_routed_experts`` is how many experts are held here, ``expert_share``
= {"rank", "of"} which of how many equal shares this is (the router has
``held * of`` outputs, the published count), ``vocab_size`` the slice of
the vocabulary.

Names are one layer's leaves as the published checkpoint has them
(``w_ukv`` is ``kv_b_proj`` whole: a head's ``k_nope | v`` columns side
by side); ``benchmark/lib/glm_program.py`` stacks them as
paddle_tpu/models/glm_moe_dsa.py holds them, ``w_ukv`` as its two halves
a head.

The draw is made so that selection and routing MATTER: ``W_uq`` and
``W_qI`` are drawn wide enough that the attention's and the indexer's
logits over unit-RMS inputs have a standard deviation of ``LOGIT_STD``
(with every matrix at ``MATRIX_STD`` both would lie under 1 and a
softmax over 2048 tokens would be near even), the router's logits one of
``ROUTER_LOGIT_STD``, and the selection bias lies on its quantiles."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import mimo_weights as MW
from .weights import MATRIX_STD, NORM_STD, _leaf_key, seed_key  # noqa: F401

LOGIT_STD = 2.5         # attention's and indexer's logits


def sizes(cfg):
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    held = cfg["n_routed_experts"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "qr": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "hdv": cfg["v_head_dim"], "hi": cfg["index_n_heads"],
            "di": cfg["index_head_dim"], "topk": cfg["index_topk"],
            "ff": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "experts": held * share["of"], "held": held,
            "first": held * share["rank"],
            "top_k": cfg["num_experts_per_tok"],
            "dense": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "theta": float(cfg["rope_parameters"]["rope_theta"])}


def kinds(cfg):
    """Per layer: ``dense`` | ``moe``."""
    z = sizes(cfg)
    return ["dense" if l < z["dense"] else "moe" for l in range(z["layers"])]


def _wide(cfg):
    """The standard deviations of ``W_uq`` and ``W_qI`` that give logits
    of ``LOGIT_STD`` over unit-RMS inputs, every other matrix at
    ``MATRIX_STD``: an attention logit is (q_nope . k_nope + q_rope .
    k_r) / sqrt(nope + rope) with k_nope of std M sqrt(rank) and k_r of
    std M sqrt(d); an indexer score sums ``hi`` heads of w relu(qI . kI)
    with kI of unit variance and w of std M sqrt(d / (hi di))."""
    z, m = sizes(cfg), MATRIX_STD
    per_q = (z["nope"] * m * m * z["rank"] + z["rope"] * m * m * z["d"]) ** 0.5
    q_std = LOGIT_STD * (z["nope"] + z["rope"]) ** 0.5 / per_q
    w_std = m * (z["d"] / (z["hi"] * z["di"])) ** 0.5
    # Var over u of sum_j w_j relu(s_j(u)) = hi w_std^2 (1/2 - 1/(2 pi)) s^2
    s_std = LOGIT_STD / (z["hi"] * w_std * w_std * (0.5 - 0.5 / jnp.pi)) ** 0.5
    return {"w_uq": float(q_std / z["qr"] ** 0.5),
            "w_qi": float(s_std / (z["di"] * z["qr"]) ** 0.5)}


def attention_leaves(cfg):
    z, wide = sizes(cfg), _wide(cfg)
    d, h, qr, rank = z["d"], z["h"], z["qr"], z["rank"]
    return {"input_ln": ((d,), "norm"), "post_ln": ((d,), "norm"),
            "w_dq": ((d, qr), "matrix"), "q_ln": ((qr,), "norm"),
            "w_uq": ((qr, h * (z["nope"] + z["rope"])), wide["w_uq"]),
            "w_dkv": ((d, rank + z["rope"]), "matrix"),
            "kv_ln": ((rank,), "norm"),
            "w_ukv": ((rank, h * (z["nope"] + z["hdv"])), "matrix"),
            "wo": ((h * z["hdv"], d), "matrix"),
            "w_qi": ((qr, z["hi"] * z["di"]), wide["w_qi"]),
            "w_ki": ((d, z["di"]), "matrix"),
            "ki_ln_g": ((z["di"],), "norm"), "ki_ln_b": ((z["di"],), "shift"),
            "w_wi": ((d, z["hi"]), "matrix")}


def dense_leaves(cfg):
    z = sizes(cfg)
    return {"w_gate": ((z["d"], z["ff"]), "matrix"),
            "w_up": ((z["d"], z["ff"]), "matrix"),
            "w_down": ((z["ff"], z["d"]), "matrix")}


def moe_leaves(cfg):
    """The router and the shared expert."""
    z = sizes(cfg)
    return {"router": ((z["d"], z["experts"]), "router"),
            "router_bias": ((z["experts"],), "selection_bias"),
            "ws_gate": ((z["d"], z["fs"]), "matrix"),
            "ws_up": ((z["d"], z["fs"]), "matrix"),
            "ws_down": ((z["fs"], z["d"]), "matrix")}


def expert_leaves(cfg):
    """One routed expert's three matrices."""
    z = sizes(cfg)
    return {"we_gate": ((z["d"], z["fe"]), "matrix"),
            "we_up": ((z["d"], z["fe"]), "matrix"),
            "we_down": ((z["fe"], z["d"]), "matrix")}


def top_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, d), "matrix"), "final_norm": ((d,), "norm"),
            "lm_head": ((d, v), "matrix")}


def _draw(key, shape, kind, dtype):
    """``kind``: a number is a matrix's own standard deviation; ``shift``
    a LayerNorm's bias; the rest are ``mimo_weights``' kinds (``matrix``,
    ``norm``, ``router`` and ``selection_bias``, float32, by its
    quantiles)."""
    if isinstance(kind, float):
        return (kind * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if kind == "shift":
        return (NORM_STD * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    return MW._draw(key, shape, kind, dtype)


def _make(key, leaves, layer, dtype):
    return {name: _draw(_leaf_key(key, name, layer), shape, how, dtype)
            for name, (shape, how) in leaves.items()}


def make_expert(key, cfg, layer, expert, dtype):
    """Routed expert ``expert`` (its id among all the router's) of
    ``layer``."""
    return {name: _draw(jax.random.fold_in(_leaf_key(key, name, layer),
                                           expert), shape, how, dtype)
            for name, (shape, how) in expert_leaves(cfg).items()}


def make_layer(key, cfg, layer, kind, dtype):
    """One layer's leaves; ``kind`` is static, ``layer`` may be traced.
    An expert layer's routed experts are the held ones, stacked."""
    out = _make(key, attention_leaves(cfg), layer, dtype)
    if kind == "dense":
        out.update(_make(key, dense_leaves(cfg), layer, dtype))
        return out
    z = sizes(cfg)
    out.update(_make(key, moe_leaves(cfg), layer, dtype))
    out.update(jax.lax.map(
        lambda e: make_expert(key, cfg, layer, e, dtype),
        z["first"] + jnp.arange(z["held"], dtype=jnp.int32)))
    return out


def make_top(key, cfg, dtype, only=None):
    return {name: _draw(_leaf_key(key, name), shape, how, dtype)
            for name, (shape, how) in top_leaves(cfg).items()
            if only is None or name in only}


def split_ukv(cfg, w_ukv):
    """``W_ukv`` [rank, H (nope + v)] as the program holds it: ``w_uk``
    [H, nope, rank] (a head's q_nope goes into the latent space by it)
    and ``w_uv`` [H, rank, v]."""
    z = sizes(cfg)
    w = w_ukv.reshape(z["rank"], z["h"], z["nope"] + z["hdv"])
    return {"w_uk": jnp.transpose(w[..., :z["nope"]], (1, 2, 0)),
            "w_uv": jnp.transpose(w[..., z["nope"]:], (1, 0, 2))}


def model_items(cfg):
    """The hashable part of a configuration that fixes the model."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "index_n_heads", "index_head_dim", "index_topk", "vocab_size",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps")
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_parameters",
         (("rope_theta", cfg["rope_parameters"]["rope_theta"]),)),
        ("expert_share", (("rank", share["rank"]), ("of", share["of"]))))


def cfg_of(items):
    cfg = dict(items)
    cfg["expert_share"] = dict(cfg["expert_share"])
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])
    return cfg


GROUPS = {"attention": attention_leaves, "dense": dense_leaves,
          "moe": moe_leaves}


@functools.partial(jax.jit, static_argnames=("cfg_items", "group", "dtype"))
def stack_on_device(key, layers, cfg_items, group, dtype):
    """The leaves of ``group`` (``attention``: norms, latent attention
    and indexer; ``dense``; ``moe``: router and shared expert) of the
    layers ``layers`` [n], stacked as the program holds them: made a
    layer at a time by one loop on the device, each leaf the very draw
    :func:`make_layer` gives for that layer."""
    cfg = cfg_of(cfg_items)

    def one(l):
        out = _make(key, GROUPS[group](cfg), l, dtype)
        if group == "attention":
            out.update(split_ukv(cfg, out.pop("w_ukv")))
        return out

    return jax.lax.map(one, layers)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def experts_on_device(key, layers, cfg_items, dtype):
    """The held experts of the expert layers ``layers`` [n] in one stack
    ``[n * held, ...]``, layer-major, an expert at a time."""
    cfg = cfg_of(cfg_items)
    z = sizes(cfg)
    layer = jnp.repeat(layers, z["held"])
    expert = jnp.tile(z["first"] + jnp.arange(z["held"], dtype=jnp.int32),
                      layers.shape[0])
    return jax.lax.map(lambda le: make_expert(key, cfg, le[0], le[1], dtype),
                       (layer, expert))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype", "only"))
def top_on_device(key, cfg_items, dtype, only=None):
    return make_top(key, cfg_of(cfg_items), dtype, only)

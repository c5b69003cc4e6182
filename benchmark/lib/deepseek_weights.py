"""Weights of a ``deepseek_v3`` configuration (DeepSeek-V3) from the seed,
drawn as ``lib/glm_weights.py`` draws GLM-5's: every leaf of layer ``l``
depends only on (seed, leaf, l), a routed expert's on its id among ALL
the router's experts besides, and the file states the chip's share as
``glm-5-serve``'s does (``n_routed_experts`` held here, ``expert_share``
= {"rank", "of"}, ``vocab_size`` the slice). The family is GLM-5's
without the indexer, so everything but the attention's leaves is that
module's own code, handed this configuration as it reads one
(:func:`glm_view`).

What differs in the draw: ``W_uq`` is drawn so wide that the attention's
logits over unit-RMS inputs have a standard deviation of ``LOGIT_STD``
WITH YaRN's scale on the softmax in (a program that forgets the scale
then softens every softmax by 1.87, which the limits of ``correct``
see), and the selection bias is a tenth of ``mimo_weights``' (``+-0.005``
by its quantiles): at router logits of std 2 the top scores of 256 lie
0.005-0.02 apart, so scores and bias decide the chosen set together
(PERF.md, Open question 18: at +-0.05 the bias decides alone)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import glm_weights as GW
from .weights import MATRIX_STD, seed_key  # noqa: F401

LOGIT_STD = GW.LOGIT_STD
BIAS_SCALE = 0.1        # of mimo_weights.SELECTION_BIAS (0.05)


def glm_view(cfg):
    """The configuration as ``glm_weights`` reads one: no indexer, the
    rotary base where that module looks for it."""
    return {**cfg, "index_n_heads": 0, "index_head_dim": 0, "index_topk": 0,
            "rope_parameters": {"rope_theta": cfg["rope_theta"]}}


def softmax_scale(cfg):
    """``(nope + rope)^-0.5 x m(mscale_all_dim)^2``, ``m(a) = 0.1 a
    ln(factor) + 1``."""
    y = cfg["rope_scaling"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def sizes(cfg):
    z = GW.sizes(glm_view(cfg))
    for k in ("hi", "di", "topk"):
        del z[k]
    z.update(n_group=cfg["n_group"], topk_group=cfg["topk_group"],
             scale=softmax_scale(cfg))
    return z


def kinds(cfg):
    return GW.kinds(glm_view(cfg))


def attention_leaves(cfg):
    """A layer's norms and latent attention. ``W_uq``'s standard
    deviation: a logit is (q_nope . k_nope + q_rope . k_r) x scale with
    k_nope of std M sqrt(rank) and k_r of std M sqrt(d)."""
    z, m = sizes(cfg), MATRIX_STD
    d, h, qr, rank = z["d"], z["h"], z["qr"], z["rank"]
    per_q = (z["nope"] * m * m * rank + z["rope"] * m * m * d) ** 0.5
    q_std = float(LOGIT_STD / (z["scale"] * per_q) / qr ** 0.5)
    return {"input_ln": ((d,), "norm"), "post_ln": ((d,), "norm"),
            "w_dq": ((d, qr), "matrix"), "q_ln": ((qr,), "norm"),
            "w_uq": ((qr, h * (z["nope"] + z["rope"])), q_std),
            "w_dkv": ((d, rank + z["rope"]), "matrix"),
            "kv_ln": ((rank,), "norm"),
            "w_ukv": ((rank, h * (z["nope"] + z["hdv"])), "matrix"),
            "wo": ((h * z["hdv"], d), "matrix")}


def _small_bias(leaves):
    leaves["router_bias"] = leaves["router_bias"] * BIAS_SCALE
    return leaves


def make_layer(key, cfg, layer, kind, dtype):
    """One layer's leaves; ``kind`` is static, ``layer`` may be traced.
    An expert layer's routed experts are the held ones, stacked."""
    g = glm_view(cfg)
    out = GW._make(key, attention_leaves(cfg), layer, dtype)
    if kind == "dense":
        out.update(GW._make(key, GW.dense_leaves(g), layer, dtype))
        return out
    z = sizes(cfg)
    out.update(_small_bias(GW._make(key, GW.moe_leaves(g), layer, dtype)))
    out.update(jax.lax.map(
        lambda e: GW.make_expert(key, g, layer, e, dtype),
        z["first"] + jnp.arange(z["held"], dtype=jnp.int32)))
    return out


def model_items(cfg):
    """The hashable part of a configuration that fixes the model:
    ``glm_weights``' items of :func:`glm_view` (so that its jitted makers
    take them) and this family's own keys behind them."""
    return GW.model_items(glm_view(cfg)) + (
        ("rope_theta", cfg["rope_theta"]),
        ("n_group", cfg["n_group"]), ("topk_group", cfg["topk_group"]),
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))))


def cfg_of(items):
    cfg = GW.cfg_of(items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def attention_on_device(key, layers, cfg_items, dtype):
    """The attention's and norms' leaves of the layers ``layers`` [n],
    stacked as the program holds them (``w_ukv`` as its two halves a
    head), each leaf the very draw :func:`make_layer` gives."""
    cfg = cfg_of(cfg_items)

    def one(l):
        out = GW._make(key, attention_leaves(cfg), l, dtype)
        out.update(GW.split_ukv(glm_view(cfg), out.pop("w_ukv")))
        return out

    return jax.lax.map(one, layers)


def moe_on_device(key, layers, cfg_items, dtype):
    """Routers (with the small bias) and shared experts, stacked."""
    return _small_bias(dict(GW.stack_on_device(key, layers, cfg_items, "moe",
                                               dtype)))

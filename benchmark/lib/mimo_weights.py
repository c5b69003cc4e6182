"""Weights of a ``mimo_v2`` configuration from the seed, made on the
device in the type they are served in. Every leaf of layer ``l`` depends
only on (seed, leaf, l), and an expert's leaves on its id among ALL the
router's experts besides: every share of one model draws the same
expert 37, and the reference makes one layer at a time and gets bit for
bit what the program was given.

The file states the chip's share: ``n_routed_experts`` is how many
experts are held here, ``expert_share`` = {"rank", "of"} which of how
many equal shares this is (the router has ``held * of`` outputs, the
published count), ``vocab_size`` the slice of the vocabulary.

Names are one layer's leaves (``wq``, ``router``, ``we_gate`` ...);
``benchmark/lib/mimo_program.py`` stacks them as
paddle_tpu/models/mimo_v2.py holds them."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .weights import MATRIX_STD, NORM_STD, _leaf_key, seed_key  # noqa: F401

SINK_STD = 1.0              # a sink logit a head, float32
SELECTION_BIAS = 0.05       # uniform in +-: changes which experts are chosen
BIAS_STRATUM = 4            # consecutive experts that share its quantiles
ROUTER_LOGIT_STD = 2.0      # the router's logits over a unit-RMS input


def sizes(cfg):
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    held = cfg["n_routed_experts"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "hd": cfg["head_dim"], "hdv": cfg["v_head_dim"],
            "kv": {"global": cfg["num_key_value_heads"],
                   "window": cfg["swa_num_key_value_heads"]},
            "ff": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"],
            "experts": held * share["of"], "held": held,
            "first": held * share["rank"], "top_k": cfg["num_experts_per_tok"],
            "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "window": cfg["sliding_window"], "vocab": cfg["vocab_size"]}


def kinds(cfg):
    """Per layer: (``global`` | ``window``, ``dense`` | ``moe``)."""
    return [("window" if a else "global", "moe" if f else "dense")
            for a, f in zip(cfg["hybrid_layer_pattern"],
                            cfg["moe_layer_freq"])]


def norm_leaves(cfg):
    d = cfg["hidden_size"]
    return {"input_ln": ((d,), "norm"), "post_ln": ((d,), "norm")}


def attention_leaves(cfg, kind):
    z = sizes(cfg)
    d, h, hd, hdv, kvh = z["d"], z["h"], z["hd"], z["hdv"], z["kv"][kind]
    leaves = {"wq": ((d, h * hd), "matrix"), "wk": ((d, kvh * hd), "matrix"),
              "wv": ((d, kvh * hdv), "matrix"),
              "wo": ((h * hdv, d), "matrix")}
    if kind == "window":
        leaves["sink"] = ((h,), "sink")
    return leaves


def dense_leaves(cfg):
    z = sizes(cfg)
    return {"w_gate": ((z["d"], z["ff"]), "matrix"),
            "w_up": ((z["d"], z["ff"]), "matrix"),
            "w_down": ((z["ff"], z["d"]), "matrix")}


def router_leaves(cfg):
    z = sizes(cfg)
    return {"router": ((z["d"], z["experts"]), "router"),
            "router_bias": ((z["experts"],), "selection_bias")}


def expert_leaves(cfg):
    """One expert's three matrices."""
    z = sizes(cfg)
    return {"we_gate": ((z["d"], z["fe"]), "matrix"),
            "we_up": ((z["d"], z["fe"]), "matrix"),
            "we_down": ((z["fe"], z["d"]), "matrix")}


def top_leaves(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": ((v, d), "matrix"), "final_norm": ((d,), "norm"),
            "lm_head": ((d, v), "matrix")}


def _draw(key, shape, kind, dtype):
    if kind == "selection_bias":
        # the uniform distribution's quantiles, each group of
        # BIAS_STRATUM consecutive experts holding all of them in an order
        # of its own (as the traffic's lengths are quantiles in a drawn
        # order): every share of the experts then carries the same set of
        # biases whatever the seed. Drawn independently, +-0.05 made the
        # load of 16 of 256 experts differ by a quarter from seed to seed
        # (0.62 to 1.46 of an even share), and every latency with it.
        groups = shape[0] // BIAS_STRATUM
        order = jax.vmap(lambda k: jax.random.permutation(k, BIAS_STRATUM))(
            jax.random.split(key, groups)).reshape(shape)
        return SELECTION_BIAS * (2.0 * (order + 0.5) / BIAS_STRATUM - 1.0)
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "sink":
        return SINK_STD * n
    if kind == "router":        # float32: the scores are stated in it
        return (ROUTER_LOGIT_STD / shape[0] ** 0.5) * n
    return (1.0 + NORM_STD * n if kind == "norm"
            else MATRIX_STD * n).astype(dtype)


def _make(key, leaves, layer, dtype):
    return {name: _draw(_leaf_key(key, name, layer), shape, how, dtype)
            for name, (shape, how) in leaves.items()}


def make_expert(key, cfg, layer, expert, dtype):
    """Expert ``expert`` (its id among all the router's) of ``layer``."""
    return {name: _draw(jax.random.fold_in(_leaf_key(key, name, layer),
                                           expert), shape, how, dtype)
            for name, (shape, how) in expert_leaves(cfg).items()}


def make_layer(key, cfg, layer, kind, dtype):
    """One layer's leaves; ``kind`` = (attention kind, ffn kind) is
    static, ``layer`` may be traced. An expert layer's experts are the
    held ones, stacked."""
    a_kind, f_kind = kind
    out = _make(key, {**norm_leaves(cfg), **attention_leaves(cfg, a_kind)},
                layer, dtype)
    if f_kind == "dense":
        out.update(_make(key, dense_leaves(cfg), layer, dtype))
        return out
    z = sizes(cfg)
    out.update(_make(key, router_leaves(cfg), layer, dtype))
    out.update(jax.lax.map(
        lambda e: make_expert(key, cfg, layer, e, dtype),
        z["first"] + jnp.arange(z["held"], dtype=jnp.int32)))
    return out


def make_top(key, cfg, dtype, only=None):
    return {name: _draw(_leaf_key(key, name), shape, how, dtype)
            for name, (shape, how) in top_leaves(cfg).items()
            if only is None or name in only}


def model_items(cfg):
    """The hashable part of a configuration that fixes the model."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
            "v_head_dim", "vocab_size", "n_routed_experts",
            "num_experts_per_tok", "scoring_func", "norm_topk_prob",
            "routed_scaling_factor", "partial_rotary_factor", "rope_theta",
            "swa_rope_theta", "sliding_window", "attention_value_scale",
            "layernorm_epsilon")
    share = cfg.get("expert_share") or {"rank": 0, "of": 1}
    return tuple((k, cfg[k]) for k in keys if cfg.get(k) is not None) + (
        ("hybrid_layer_pattern", tuple(cfg["hybrid_layer_pattern"])),
        ("moe_layer_freq", tuple(cfg["moe_layer_freq"])),
        ("expert_share", (("rank", share["rank"]), ("of", share["of"]))))


def cfg_of(items):
    cfg = dict(items)
    cfg["expert_share"] = dict(cfg["expert_share"])
    return cfg


GROUPS = {"norms": norm_leaves,
          "global": functools.partial(attention_leaves, kind="global"),
          "window": functools.partial(attention_leaves, kind="window"),
          "dense": dense_leaves, "moe": router_leaves}


@functools.partial(jax.jit, static_argnames=("cfg_items", "group", "dtype"))
def stack_on_device(key, layers, cfg_items, group, dtype):
    """The leaves of ``group`` (``norms``, ``global``, ``window``,
    ``dense`` or ``moe``: the routers) of the layers ``layers`` [n],
    stacked: made a layer at a time by one loop on the device, each leaf
    the very draw :func:`make_layer` gives for that layer."""
    leaves = GROUPS[group](cfg_of(cfg_items))
    return jax.lax.map(lambda l: _make(key, leaves, l, dtype), layers)


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

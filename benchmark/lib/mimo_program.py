"""The builder of a ``mimo_v2`` serve configuration (``program.build``
in its file): ``build_model(cfg, seed)`` gives the program's
``MimoV2ForCausalLM`` holding the benchmark's weights for the chip's
share of the experts and of the vocabulary (``lib/mimo_weights.py``,
``sizes``), ``kv_bytes_per_block(cfg, block_size)`` what one block of the
paged cache takes, which only the global layers fill."""

from __future__ import annotations

import dataclasses
import gc

from . import mimo_weights as W

POOL_LANES = 128        # the pool holds a key head in whole lane tiles


def mimo_config(cfg, **overrides):
    """A ``MimoV2Config`` from a configuration file: the published keys
    the dataclass knows, the share (the router's width is the held count
    times the shares; ``held_experts`` = (first, count)), then the
    file's own ``program.model`` keys."""
    from paddle_tpu.models.mimo_v2 import MimoV2Config
    known = {f.name for f in dataclasses.fields(MimoV2Config)}
    kw = {k: v for k, v in cfg.items() if k in known}
    z = W.sizes(cfg)
    kw["n_routed_experts"] = z["experts"]
    kw["held_experts"] = (z["first"], z["held"])
    kw.update(cfg.get("program", {}).get("model", {}))
    kw.update(overrides)
    return MimoV2Config(**kw)


def kv_bytes_per_block(cfg, block_size, itemsize=2):
    """Keys (as the pool holds them, padded to whole lane tiles) and
    values of every global layer and kv head for ``block_size`` tokens;
    a window layer keeps nothing in the pool."""
    z = W.sizes(cfg)
    n_global = sum(a == "global" for a, _ in W.kinds(cfg))
    key_lanes = -(-z["hd"] // POOL_LANES) * POOL_LANES
    return (n_global * z["kv"]["global"] * block_size
            * (key_lanes + z["hdv"]) * itemsize)


def build_model(cfg, seed):
    """The model object built two layers deep at widths of 8 (its own
    draw of the configuration's would hold a float32 copy beside the
    leaves, and take its time), then every leaf replaced by the seeded
    one: made on the device a layer (an
    expert) at a time, in the served type, stacked as the program holds
    them."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM
    paddle.seed(int(seed) % (2 ** 31))
    full = mimo_config(cfg)
    model = MimoV2ForCausalLM(mimo_config(
        cfg, num_hidden_layers=2, hybrid_layer_pattern=(0, 1),
        moe_layer_freq=(0, 1), vocab_size=8, hidden_size=8,
        intermediate_size=8, moe_intermediate_size=8, num_attention_heads=1,
        num_key_value_heads=1, swa_num_key_value_heads=1, head_dim=8,
        v_head_dim=8, n_routed_experts=2, held_experts=(0, 1)))
    names = [n for n, p in model._parameters.items() if p is not None]
    for n in names:                       # free the shallow leaves first
        model._parameters[n]._in_place_update(jnp.zeros((), jnp.bfloat16))
    gc.collect()
    dtype = jnp.dtype(full.dtype)
    key, items = W.seed_key(seed), W.model_items(cfg)
    made = dict(W.top_on_device(key, items, dtype))
    layers_of = lambda kind: jnp.asarray(
        [l for l, k in enumerate(W.kinds(cfg)) if kind in k], jnp.int32)
    made.update(W.stack_on_device(
        key, jnp.arange(full.num_hidden_layers, dtype=jnp.int32), items,
        "norms", dtype))
    for kind, tag in (("global", "_g"), ("window", "_w")):
        stack = W.stack_on_device(key, layers_of(kind), items, kind, dtype)
        made.update({n + tag if n != "sink" else n: v
                     for n, v in stack.items()})
    for kind in ("dense", "moe"):
        made.update(W.stack_on_device(key, layers_of(kind), items, kind,
                                      dtype))
    made.update(W.experts_on_device(key, layers_of("moe"), items, dtype))
    if set(made) != set(names):
        raise AssertionError(f"the program's leaves {sorted(names)} are not "
                             f"the benchmark's {sorted(made)}")
    for n in names:
        model._parameters[n]._in_place_update(made[n])
    model.config = full
    return model

"""The builder of a ``deepseek_v3`` serve configuration (``program.build``
in its file): ``build_model(cfg, seed)`` gives the program's
``DeepseekV3ForCausalLM`` holding the benchmark's weights for the chip's
share of the experts and of the vocabulary (``lib/deepseek_weights.py``,
``sizes``), ``kv_bytes_per_block(cfg, block_size)`` what one block of the
paged cache takes: ONE latent page a layer, there is no second pool."""

from __future__ import annotations

import dataclasses
import gc

from . import deepseek_weights as W
from . import glm_weights as GW
from .glm_program import POOL_LANES


def deepseek_config(cfg, **overrides):
    """A ``DeepseekV3Config`` from a configuration file: the published
    keys the dataclass knows, ``rope_scaling`` as its fields, the share
    (the router's width is the held count times the shares;
    ``held_experts`` = (first, count)), then the file's own
    ``program.model`` keys."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    known = {f.name for f in dataclasses.fields(DeepseekV3Config)}
    kw = {k: v for k, v in cfg.items() if k in known}
    kw.update({"rope_type" if k == "type" else k: v
               for k, v in cfg["rope_scaling"].items()})
    z = W.sizes(cfg)
    kw["n_routed_experts"] = z["experts"]
    kw["held_experts"] = (z["first"], z["held"])
    kw.update(cfg.get("program", {}).get("model", {}))
    kw.update(overrides)
    return DeepseekV3Config(**kw)


def kv_bytes_per_block(cfg, block_size, itemsize=2):
    """A latent (as the pool holds it, padded to whole lane tiles) of
    every layer for ``block_size`` tokens."""
    z = W.sizes(cfg)
    lanes = -(-(z["rank"] + z["rope"]) // POOL_LANES) * POOL_LANES
    return z["layers"] * block_size * lanes * itemsize


def build_model(cfg, seed):
    """The model object built two layers deep at widths of 8, then every
    leaf replaced by the seeded one, made on the device a layer (an
    expert) at a time in the served type and stacked as the program
    holds them (``glm_program.build_model``'s way)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    paddle.seed(int(seed) % (2 ** 31))
    full = deepseek_config(cfg)
    model = DeepseekV3ForCausalLM(deepseek_config(
        cfg, num_hidden_layers=2, first_k_dense_replace=1, vocab_size=8,
        hidden_size=8, intermediate_size=8, moe_intermediate_size=8,
        num_attention_heads=1, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        n_routed_experts=2, held_experts=(0, 1), num_experts_per_tok=1,
        n_group=1, topk_group=1))
    names = [n for n, p in model._parameters.items() if p is not None]
    for n in names:                       # free the shallow leaves first
        model._parameters[n]._in_place_update(jnp.zeros((), jnp.bfloat16))
    gc.collect()
    dtype = jnp.dtype(full.dtype)
    key, items = W.seed_key(seed), W.model_items(cfg)
    kinds = W.kinds(cfg)
    layers_of = lambda kind: jnp.asarray(
        [l for l, k in enumerate(kinds) if k == kind], jnp.int32)
    made = dict(GW.top_on_device(key, items, dtype))
    made.update(W.attention_on_device(
        key, jnp.arange(len(kinds), dtype=jnp.int32), items, dtype))
    made.update(GW.stack_on_device(key, layers_of("dense"), items, "dense",
                                   dtype))
    made.update(W.moe_on_device(key, layers_of("moe"), items, dtype))
    made.update(GW.experts_on_device(key, layers_of("moe"), items, dtype))
    if set(made) != set(names):
        raise AssertionError(f"the program's leaves {sorted(names)} are not "
                             f"the benchmark's {sorted(made)}")
    for n in names:
        model._parameters[n]._in_place_update(made[n])
    model.config = full
    return model

"""The builder of a ``glm_moe_dsa`` serve configuration (``program.build``
in its file): ``build_model(cfg, seed)`` gives the program's
``GlmMoeDsaForCausalLM`` holding the benchmark's weights for the chip's
share of the experts and of the vocabulary (``lib/glm_weights.py``,
``sizes``), ``kv_bytes_per_block(cfg, block_size)`` what one block of the
paged cache takes: a latent page and an indexer-key page a layer."""

from __future__ import annotations

import dataclasses
import gc

from . import glm_weights as W

POOL_LANES = 128        # the pool holds a latent in whole lane tiles


def glm_config(cfg, **overrides):
    """A ``GlmMoeDsaConfig`` from a configuration file: the published
    keys the dataclass knows, ``rope_parameters`` as its two fields, the
    share (the router's width is the held count times the shares;
    ``held_experts`` = (first, count)), then the file's own
    ``program.model`` keys."""
    from paddle_tpu.models.glm_moe_dsa import GlmMoeDsaConfig
    known = {f.name for f in dataclasses.fields(GlmMoeDsaConfig)}
    kw = {k: v for k, v in cfg.items() if k in known}
    kw.update(cfg.get("rope_parameters", {}))
    z = W.sizes(cfg)
    kw["n_routed_experts"] = z["experts"]
    kw["held_experts"] = (z["first"], z["held"])
    kw.update(cfg.get("program", {}).get("model", {}))
    kw.update(overrides)
    return GlmMoeDsaConfig(**kw)


def kv_bytes_per_block(cfg, block_size, itemsize=2):
    """A latent (as the pool holds it, padded to whole lane tiles) and an
    indexer key of every layer for ``block_size`` tokens."""
    z = W.sizes(cfg)
    lanes = -(-(z["rank"] + z["rope"]) // POOL_LANES) * POOL_LANES
    return z["layers"] * block_size * (lanes + z["di"]) * itemsize


def build_model(cfg, seed):
    """The model object built two layers deep at widths of 8 (its own
    draw of the configuration's would hold a float32 copy beside the
    leaves, and take its time), then every leaf replaced by the seeded
    one: made on the device a layer (an expert) at a time, in the served
    type, stacked as the program holds them."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.glm_moe_dsa import GlmMoeDsaForCausalLM
    paddle.seed(int(seed) % (2 ** 31))
    full = glm_config(cfg)
    model = GlmMoeDsaForCausalLM(glm_config(
        cfg, num_hidden_layers=2, first_k_dense_replace=1, vocab_size=8,
        hidden_size=8, intermediate_size=8, moe_intermediate_size=8,
        num_attention_heads=1, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        index_n_heads=1, index_head_dim=8, n_routed_experts=2,
        held_experts=(0, 1)))
    names = [n for n, p in model._parameters.items() if p is not None]
    for n in names:                       # free the shallow leaves first
        model._parameters[n]._in_place_update(jnp.zeros((), jnp.bfloat16))
    gc.collect()
    dtype = jnp.dtype(full.dtype)
    key, items = W.seed_key(seed), W.model_items(cfg)
    made = dict(W.top_on_device(key, items, dtype))
    kinds = W.kinds(cfg)
    layers_of = lambda kind: jnp.asarray(
        [l for l, k in enumerate(kinds) if k == kind], jnp.int32)
    made.update(W.stack_on_device(
        key, jnp.arange(len(kinds), dtype=jnp.int32), items, "attention",
        dtype))
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

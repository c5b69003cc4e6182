"""The builder of a ``granitemoehybrid`` serve configuration
(``program.build`` in its file): ``build_model(cfg, seed)`` gives the
program's ``GraniteHybridForCausalLM`` holding the benchmark's weights,
``kv_bytes_per_block(cfg, block_size)`` what one block of the paged cache
takes, which only the attention layers fill."""

from __future__ import annotations

import dataclasses
import functools
import gc

import jax

from . import granite_weights as W


def granite_config(cfg, **overrides):
    """A ``GraniteHybridConfig`` from a configuration file: the published
    keys the dataclass knows (``shared_intermediate_size`` is its
    feed-forward width), then the file's own ``program.model`` keys."""
    from paddle_tpu.models.granite_hybrid import GraniteHybridConfig
    known = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
    kw = {k: v for k, v in cfg.items() if k in known}
    kw["intermediate_size"] = cfg["shared_intermediate_size"]
    kw.update(cfg.get("program", {}).get("model", {}))
    kw.update(overrides)
    return GraniteHybridConfig(**kw)


def kv_bytes_per_block(cfg, block_size, itemsize=2):
    """Keys and values of every attention layer and kv head for
    ``block_size`` tokens; a Mamba layer keeps nothing in the pool."""
    z = W.sizes(cfg)
    n_attention = sum(t == "attention" for t in cfg["layer_types"])
    return 2 * n_attention * z["kvh"] * block_size * z["ahd"] * itemsize


@functools.partial(jax.jit, static_argnums=1)
def _split_in_proj(in_proj, at):
    return in_proj[:, :, :at], in_proj[:, :, at:]


def build_model(cfg, seed):
    """The model object built one layer of each kind deep (its own draw
    of the full depth would hold a float32 copy beside the leaves), then
    every leaf replaced by the seeded one: made on the device a layer at
    a time, in the served type, and stacked over the layers of its
    kind."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    paddle.seed(int(seed) % (2 ** 31))
    full = granite_config(cfg)
    model = GraniteHybridForCausalLM(granite_config(
        cfg, num_hidden_layers=2, layer_types=("mamba", "attention")))
    names = [n for n, p in model._parameters.items() if p is not None]
    for n in names:                       # free the shallow leaves first
        model._parameters[n]._in_place_update(jnp.zeros((), jnp.bfloat16))
    gc.collect()
    dtype = jnp.dtype(full.dtype)
    key, items = W.seed_key(seed), W.model_items(cfg)
    made = dict(W.top_on_device(key, items, dtype))
    of_kind = lambda kind: jnp.asarray(
        [l for l, t in enumerate(full.layer_types) if t == kind], jnp.int32)
    made.update(W.stack_on_device(
        key, jnp.arange(full.num_hidden_layers, dtype=jnp.int32), items,
        "shared", dtype))
    for kind in ("mamba", "attention"):
        made.update(W.stack_on_device(key, of_kind(kind), items, kind, dtype))
    # the program holds the published in_proj as its z | xBC columns and
    # its dt columns (models/granite_hybrid.py, ``_in_proj``)
    made["in_proj"], made["dt_proj"] = _split_in_proj(
        made["in_proj"], W.sizes(cfg)["di"] + W.sizes(cfg)["conv"])
    if set(made) != set(names):
        raise AssertionError(f"the program's leaves {sorted(names)} are not "
                             f"the benchmark's {sorted(made)}")
    for n in names:
        model._parameters[n]._in_place_update(made[n])
    model.config = full
    return model

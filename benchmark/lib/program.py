"""The few calls by which the benchmark stands the system under test up:
the model object, its weights from the seed, the persistent compilation
cache. Everything it touches is the program's public surface.

This module is also the default builder of a serve configuration
(``lib/manifest.py``, ``serve_modules``): it gives ``build_model(cfg,
seed)`` and ``kv_bytes_per_block(cfg, block_size)`` for a model that is
``LlamaForCausalLM``. A configuration whose model is not names a builder
of its own in its file and edits nothing here."""

from __future__ import annotations

import dataclasses
import gc


def enable_compile_cache():
    """JAX's persistent cache, where the program keeps it: the directory
    JAX_COMPILATION_CACHE_DIR names, else ``<checkout>/.jax_cache``."""
    from paddle_tpu.utils.compile_cache import enable_compile_cache as on
    return on()


def llama_config(cfg, **overrides):
    """A ``LlamaConfig`` from a configuration file: the published keys
    the dataclass knows, then the file's own ``program.model`` keys."""
    from paddle_tpu.models.llama import LlamaConfig
    known = {f.name for f in dataclasses.fields(LlamaConfig)}
    kw = {k: v for k, v in cfg.items() if k in known}
    kw.update(cfg.get("program", {}).get("model", {}))
    kw.update(overrides)
    return LlamaConfig(**kw)


def kv_bytes_per_block(cfg, block_size, itemsize=2):
    """Bytes one block of the paged cache takes: keys and values of every
    layer and kv head for ``block_size`` tokens."""
    from . import weights
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * block_size * weights.head_dim(cfg) * itemsize)


def build_model(cfg, seed):
    """The program's model holding the benchmark's weights.

    ``LlamaForCausalLM`` draws float32 random weights leaf by leaf and
    casts them (``LazyGuard`` is a no-op): at the cells' sizes that is
    a transient of 9 GB beside the leaves already made, which does not
    fit one chip. So the object is built one layer deep, its depth is
    set to the configuration's, and every leaf is replaced by the seeded
    one, made on the device in one jitted call in the served type."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from . import weights
    paddle.seed(int(seed) % (2 ** 31))
    depth = cfg["num_hidden_layers"]
    model = LlamaForCausalLM(llama_config(cfg, num_hidden_layers=1))
    names = [n for n, p in model._parameters.items() if p is not None]
    for n in names:                       # free the shallow leaves first
        model._parameters[n]._in_place_update(jnp.zeros((), jnp.bfloat16))
    gc.collect()
    made = weights.make_all(seed, cfg, jnp.dtype(model.config.dtype))
    if set(made) != set(names):
        raise AssertionError(f"the program's leaves {sorted(names)} are not "
                             f"the benchmark's {sorted(made)}")
    for n in names:
        model._parameters[n]._in_place_update(made[n])
    model.config.num_hidden_layers = depth
    return model

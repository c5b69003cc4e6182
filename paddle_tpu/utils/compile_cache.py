"""JAX's persistent compilation cache, placed from outside.

The entry scripts (``chip_smoke.py``, ``bench.py``, ``__graft_entry__.py``)
call :func:`enable_compile_cache` before their first use of JAX, so that
a second run in the same checkout finds the step programs and kernels of
the first. ``import paddle_tpu`` does not call it: the test suite runs
with the cache off.

The directory is part of the cache's key, so it never carries a
temporary name, a pid or a time:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory;
- unset: ``<checkout>/.jax_cache``, resolved from this file's own
  location (the same path from any working directory; ``.gitignore``
  lists it).
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` — this file sits at paddle_tpu/utils/.
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the default floor (1 s) would drop the kernels and the smaller
    # step programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

"""Test config: force CPU with 8 virtual devices so sharding/collective
tests run without TPU hardware (SURVEY §4: the reference tests multi-device
via multi-process on localhost; the JAX analogue is a virtual device mesh).

``JAX_PLATFORMS=cpu`` (the tier-1 command sets it) is honoured; the
config update below says the same for a bare ``pytest`` and adds the
eight devices, before any backend initialization.

The suite compiles thousands of small programs and runs each a few
times, so the process (and every child it starts) asks the CPU compiler
for its cheapest code by its quickest route (no optimization passes, the
older direct emitter for fusions), and keeps what it compiled in a cache
of the session: an engine test builds the same programs as its neighbour, in
new closures that only the persistent cache recognises. Both go through
the environment, before ``jax`` is imported; a directory the caller
already chose is left alone."""

import atexit
import os
import shutil
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_backend_optimization_level=0"
    + " --xla_llvm_disable_expensive_passes=true"
    + " --xla_cpu_use_fusion_emitters=false").strip()
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache_dir = tempfile.mkdtemp(prefix="paddle_tpu_tests_jax_cache_")
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import per_test_clock  # noqa: E402

#: Seconds one test may take. The longest takes about a tenth of it.
TEST_LIMIT_S = 180


@pytest.fixture(autouse=True)
def _clock(request):
    with per_test_clock(request.node.nodeid, TEST_LIMIT_S):
        yield


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)  # staticcheck: disable=SC04 — the fixture that seeds replay
    yield


#: Memory mappings the process may hold before the programs it compiled
#: are let go. Every compiled program keeps three or four (its code, its
#: constants), the engines and models that tests share keep theirs alive,
#: and the kernel refuses a process its 65530th (``vm.max_map_count``):
#: the next compile then dies in the middle of a test with a
#: segmentation fault, nine tenths of the way through the suite.
MAPS_LIMIT = 40000


@pytest.fixture(scope="module", autouse=True)
def _bounded_memory_maps():
    """After a module, let the compiled programs go if the process holds
    too many mappings; the session's persistent cache reads back what a
    later test asks for again."""
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:
        return
    if held > MAPS_LIMIT:
        import gc
        jax.clear_caches()
        gc.collect()

"""Test config: force CPU with 8 virtual devices so sharding/collective
tests run without TPU hardware (SURVEY §4: the reference tests multi-device
via multi-process on localhost; the JAX analogue is a virtual device mesh).

``JAX_PLATFORMS=cpu`` (the tier-1 command sets it) is honoured; the
config update below says the same for a bare ``pytest`` and adds the
eight devices, before any backend initialization."""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)  # staticcheck: disable=SC04 — the fixture that seeds replay
    yield

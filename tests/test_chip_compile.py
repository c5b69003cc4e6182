"""Ahead-of-time compiles for the chip, without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``v5e:2x2``). Interpret mode checks
a kernel's arithmetic; only this checks what the chip's compiler accepts:
tile alignment of a DMA slice, vector layouts, scalar memory, dot
precision, partitioning under a mesh. Every kernel the main path
dispatches to is compiled here at the Llama-3-8B widths ``chip_smoke.py``
runs (32 heads / 8 kv, head_dim 128), with ``import paddle_tpu`` — and so
the process-wide ``"high"`` matmul precision — in effect as in
production. Nothing runs, so these say nothing about results or times.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

import paddle_tpu  # noqa: E402,F401  (sets the global matmul precision)
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402

KVH, G, HD = 8, 4, 128          # llama3-8b: 32 heads / 8 kv, head_dim 128
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# -- the programs ------------------------------------------------------------
# Each builder takes ``place(shape, dtype, spec=P())`` — a ShapeDtypeStruct
# on the described device(s) — and returns (function, arguments).

def _paged_decode(block, pool_dtype, n_pages, batch=8, s_max=2048):
    def build(place):
        mb = s_max // block
        pool = place((n_pages, KVH, block, HD), pool_dtype)
        args = [place((batch, KVH, G, HD), BF16), pool, pool,
                place((batch, mb), I32), place((batch,), I32)]
        if pool_dtype == I8:
            sc = place((n_pages, KVH), F32)
            return (lambda q, k, v, t, n, ks, vs: pa.paged_attention_pallas(
                q, k, v, t, n, kv_scales=(ks, vs))), args + [sc, sc]
        return pa.paged_attention_pallas, args
    return build


def _mixed(window, block=16, n_pages=4096, batch=4, s_max=2048):
    def build(place):
        pool = place((n_pages, KVH, block, HD), BF16)
        return pa.mixed_attention_pallas, [
            place((batch, window, KVH, G, HD), BF16), pool, pool,
            place((batch, s_max // block), I32), place((batch,), I32),
            place((batch,), I32)]
    return build


def _flash(batch, seq, heads, kv_heads, grad):
    def build(place):
        def fwd(q, k, v):
            return fa.flash_attention(q, k, v, causal=True)

        def loss(q, k, v):
            return fwd(q, k, v).astype(F32).sum()

        fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
        return fn, [place((batch, seq, h, HD), BF16)
                    for h in (heads, kv_heads, kv_heads)]
    return build


def _paged_decode_tp4(block=16, n_pages=4096, batch=4, s_max=2048):
    """The tp engine's form: the kernel inside ``shard_map``, pools and
    query heads split over four chips (2 kv heads each)."""
    def build(place, mesh):
        heads = P(None, "tp", None, None)
        pool = place((n_pages, KVH, block, HD), BF16, heads)
        fn = jax.shard_map(
            pa.paged_attention_pallas, mesh=mesh,
            in_specs=(heads, heads, heads, P(), P()), out_specs=heads)
        return fn, [place((batch, KVH, G, HD), BF16, heads), pool, pool,
                    place((batch, s_max // block), I32),
                    place((batch,), I32)]
    build.mesh_axes = ((4,), ("tp",))
    return build


def _flash_train_dp2_mp2(batch=6, seq=2048, heads=32):
    """What the dp2 x mp2 train step asks of attention: the model's
    ``_attention`` under a GSPMD mesh, forward and backward. GSPMD cannot
    partition the kernel, so ``_attention`` must bring its own
    ``shard_map``."""
    def build(place, mesh):
        from paddle_tpu.distributed.fleet.mp_layers import sharding_ctx
        from paddle_tpu.models import llama

        def loss(q, k, v):
            with sharding_ctx(mesh):
                return llama._attention(q, k, v, causal=True).astype(
                    F32).sum()

        spec = P("dp", None, "mp", None)
        return jax.grad(loss, argnums=(0, 1, 2)), [
            place((batch, seq, h, HD), BF16, spec)
            for h in (heads, KVH, KVH)]
    build.mesh_axes = ((2, 2), ("dp", "mp"))
    return build


CASES = {
    "paged_decode_bf16_block16": _paged_decode(16, BF16, 4096),
    # the pool an engine could really hold on 16 GB (2 GiB each of K and
    # V codes here): scalar memory must not grow with it
    "paged_decode_int8_block32_64k_pages": _paged_decode(32, I8, 65536),
    "mixed_bf16_window16": _mixed(16),
    "mixed_bf16_window256": _mixed(256),
    "flash_fwd_b6_s2048_h32_kv8": _flash(6, 2048, 32, 8, grad=False),
    "flash_fwd_b1_s32768_h32_kv8": _flash(1, 32768, 32, 8, grad=False),
    "flash_fwd_b2_s8192_h16_kv16": _flash(2, 8192, 16, 16, grad=False),
    "flash_bwd_b6_s2048_h32_kv8": _flash(6, 2048, 32, 8, grad=True),
    "flash_bwd_b1_s32768_h32_kv8": _flash(1, 32768, 32, 8, grad=True),
    "flash_bwd_b2_s8192_h16_kv16": _flash(2, 8192, 16, 16, grad=True),
    "paged_decode_bf16_tp4_shard_map": _paged_decode_tp4(),
    "flash_fwd_bwd_dp2_mp2_gspmd": _flash_train_dp2_mp2(),
}


@pytest.mark.parametrize("name", list(CASES))
def test_compiles_for_v5e(name, topo, monkeypatch):
    build = CASES[name]
    # code that asks the backend sees the CPU here; the TPU branch is
    # what is being compiled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh_axes = getattr(build, "mesh_axes", None)
    if mesh_axes is None:
        one = SingleDeviceSharding(topo.devices[0])

        def place(shape, dtype, spec=None):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        fn, args = build(place)
    else:
        shape, names = mesh_axes
        n = int(np.prod(shape))
        mesh = Mesh(np.asarray(topo.devices[:n]).reshape(shape), names)

        def place(shape, dtype, spec=P()):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))

        fn, args = build(place, mesh)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()

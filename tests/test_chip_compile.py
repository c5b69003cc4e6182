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

import functools
import os
import re
import time
from unittest import mock

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


def _shapes_only():
    """While a model is built whose weights are handed to the compiler
    as shapes, its leaves are zeros: drawing 100 M normal values a case
    was most of what the engine cases took."""
    from paddle_tpu.nn import initializer as I
    from paddle_tpu.core.dtype import convert_dtype
    zeros = lambda self, shape, dtype=None: jnp.zeros(
        tuple(shape), convert_dtype(dtype or "float32"))
    return mock.patch.object(I.Normal, "__call__", zeros)


# -- the programs ------------------------------------------------------------
# Each builder takes ``place(shape, dtype, spec=P())`` — a ShapeDtypeStruct
# on the described device(s) — and returns (function, arguments).

def _paged_decode(block, pool_dtype, n_pages, layers=2, batch=8,
                  s_max=2048, kv_heads=KVH, group=G):
    """The decode kernel as the layer scan launches it: the stacked
    pools where they lie, the layer index as data."""
    def build(place):
        mb = s_max // block
        pool = place((layers, n_pages, kv_heads, block, HD), pool_dtype)
        args = [place((batch, kv_heads, group, HD), BF16), pool, pool,
                place((batch, mb), I32), place((batch,), I32),
                place((), I32)]
        if pool_dtype == I8:
            sc = place((layers, n_pages, kv_heads), F32)
            return (lambda q, k, v, t, n, l, ks, vs:
                    pa.paged_attention_pallas(
                        q, k, v, t, n, l, kv_scales=(ks, vs))), \
                args + [sc, sc]
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


def _paged_decode_tp4(block=16, n_pages=4096, layers=2, batch=4,
                      s_max=2048):
    """The tp engine's form: the kernel inside ``shard_map``, stacked
    pools and query heads split over four chips (2 kv heads each)."""
    def build(place, mesh):
        heads = P(None, "tp", None, None)
        pools = P(None, None, "tp", None, None)
        pool = place((layers, n_pages, KVH, block, HD), BF16, pools)
        fn = jax.shard_map(
            pa.paged_attention_pallas, mesh=mesh,
            in_specs=(heads, pools, pools, P(), P(), P()),
            out_specs=heads)
        return fn, [place((batch, KVH, G, HD), BF16, heads), pool, pool,
                    place((batch, s_max // block), I32),
                    place((batch,), I32), place((), I32)]
    build.mesh_axes = ((4,), ("tp",))
    return build


def _engine_decode(kv_dtype, block, tp=1, layers=2, kv_heads=4,
                   n_pages=4608, batch=8, s_max=512):
    """The engine's OWN ``decode_chunk_paged`` program (a chunk of decode
    steps, each a scan over the layers), at real head widths over pools
    larger than the chip's fast memory, so that what the compiler does
    with a pool here is what it does with a deployment's. ``tp`` > 1: the
    tp engine's program, under ``shard_map`` with the pools split by kv
    head. The pool's size must be no cost of a step: see
    :func:`_assert_pools_stay_put`."""
    def build(place, mesh=None):
        import paddle_tpu as paddle
        from paddle_tpu.inference import sharding
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        with _shapes_only():
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=1024, hidden_size=8 * HD, intermediate_size=1024,
                num_hidden_layers=layers, num_attention_heads=8,
                num_key_value_heads=kv_heads, attention_bias=True,
                dtype="bfloat16"))
        model.eval()
        # a described chip holds nothing: where the tp engine places its
        # weights and pools on the mesh, keep the host arrays (only their
        # shapes are read here)
        with mock.patch.object(jax, "device_put", lambda a, *_, **__: a):
            eng = DecodeEngine(model, capacity=batch, s_max=s_max,
                               block_size=block, n_blocks=n_pages,
                               kv_dtype=kv_dtype, mesh=mesh)
            stacked, *rest = eng._weights()
        wsp = sharding.stacked_weight_specs(eng._names, "tp")
        ssp = sharding.quant_scale_specs(eng._scales, "tp")
        psp = sharding.pool_specs(eng._n_pool, "tp")

        def like(a, spec=P()):
            return place(a.shape, a.dtype, spec)

        return eng._decode, [
            {n: like(v, wsp[n]) for n, v in stacked.items()},
            *jax.tree.map(like, rest),
            {n: like(v, ssp[n]) for n, v in eng._scales.items()},
            *(like(jnp.asarray(a))
              for a in (eng._tok, eng._tables, eng._lens)),
            *map(like, eng._pool(), psp)]
    if tp > 1:
        build.mesh_axes = ((tp,), ("tp",))
    build.check = functools.partial(
        _assert_pools_stay_put,
        pool=jax.ShapeDtypeStruct(
            (layers, n_pages, kv_heads // tp, block, HD),
            I8 if kv_dtype == "int8" else BF16))
    return build


def _engine_prefill(s_max=2560, n_pages=4577, layers=14, batch=32,
                    block=16, tail=None):
    """The engine's OWN prefill programs at a cell's sizes: Qwen2-7B's
    widths (3584 / 18944, 28 heads over 4 kv heads of 128, q/k/v
    biases), 14 layers. ``tail=None``: the cold ``prefill_paged`` at the
    chat cell's window of 2560 and 4577 pages. ``tail=n``: the prefix
    program (a prefix hit's, a prefill chunk's, a verify window's) for
    the bucket of ``n`` tail tokens. The vocabulary is cut (it is one
    matmul after the loop). The model is drawn one layer deep and
    handed over as shapes of 14. Both take the pools donated and must
    leave them where they lie: :func:`_assert_pools_stay_put`."""
    d, ff, kv = 28 * HD, 18944, 4 * HD

    def build(place):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        with _shapes_only():
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=1024, hidden_size=d, intermediate_size=ff,
                num_hidden_layers=1, num_attention_heads=28,
                num_key_value_heads=4, attention_bias=True, rope_theta=1e6,
                rms_norm_eps=1e-6, dtype="bfloat16"))
        model.eval()
        model.config.num_hidden_layers = layers
        eng = DecodeEngine(model, capacity=batch, s_max=s_max,
                           block_size=block, n_blocks=n_pages)
        stacked, *rest = eng._weights()

        def like(a):
            return place(a.shape, a.dtype)

        if tail is None:
            fn, data = eng._prefill, [place((1, s_max), I32),
                                      place((1,), I32)]
        else:
            fn, data = eng._prefix_prefill_for(tail), [
                place((1, tail), I32), place((1,), I32), place((1,), I32)]
        return fn, [
            {n: place((layers,) + v.shape[1:], v.dtype)
             for n, v in stacked.items()},
            *jax.tree.map(like, rest), {}, *data,
            place((eng._max_blocks,), I32), *map(like, eng._pool())]
    build.kernel = False
    build.lower_seconds = 1.0
    pool = jax.ShapeDtypeStruct((layers, n_pages, 4, block, HD), BF16)
    slice_bytes = int(np.prod(pool.shape[1:])) * pool.dtype.itemsize
    layer_bytes = 2 * (2 * d * d + 2 * d * kv + 3 * d * ff)  # matrices

    def check(compiled):
        if tail is not None:
            return _assert_pools_stay_put(compiled, pool)
        _assert_no_square_scores(compiled, s_max)
        # the block walk's layer scan slices one layer's weights off the
        # stack (466 MB of the 467.5 MB this program's temporaries are):
        # beside them, less than one layer's slice of a pool
        _assert_pools_stay_put(compiled, pool,
                               temp_below=layer_bytes + slice_bytes)
    build.check = check
    return build


_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(")
# a tuple, a loop or a pointer to part of one computes and moves nothing
_NO_WORK = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call", "optimization-barrier"}


def _hlo_instructions(text):
    """(computation it stands in, result shape, operation, line) of each
    instruction of a compiled module's text that does work."""
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        m = _HLO_OP.match(line)
        if m and m.group(3) not in _NO_WORK:
            yield comp, m.group(2), m.group(3), line


def _assert_pools_stay_put(compiled, pool, temp_below=None):
    """No instruction of the compiled module produces a value of the
    size of a pool ``[L, N, kvh, bs, hd]`` or of one layer's slice of
    one, except a write INTO the pool it is handed: the scatter of the
    rows' pages (alone or as the root of a fusion), whose result is its
    operand's own buffer. That the buffer is shared and not a second
    one is what ``memory_analysis`` shows: the program's temporaries
    stay under one layer's slice, or under ``temp_below`` bytes where a
    program's own workspace is larger than that."""
    text = compiled.as_text()
    dims = [",".join(map(str, pool.shape[i:])) for i in (0, 1)]
    roots, comp = {}, None            # computation name -> its ROOT's op
    for line in text.splitlines():
        head = re.match(r"^%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        m = _HLO_OP.match(line)
        if m and line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(3)
    moved = []
    for _, shape, op, line in _hlo_instructions(text):
        if not any(f"[{d}]" in shape for d in dims):
            continue
        if op == "fusion":
            op = roots[re.search(r"calls=%?([\w.\-]+)", line).group(1)]
        if op != "scatter":
            moved.append(line.strip()[:200])
    assert not moved, "\n".join(moved)
    if temp_below is None:
        temp_below = int(np.prod(pool.shape[1:])) * pool.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_below, (temp, temp_below)


def _assert_no_square_scores(compiled, s_max):
    """The cold prefill's work follows its prompt: no instruction
    produces square scores (a value with two dimensions of the window's
    width, give or take a block: 512 rows at the most)."""
    wide = range(s_max, s_max + 512 + 1)
    square = []
    for _, shape, _, line in _hlo_instructions(compiled.as_text()):
        for dims in re.findall(r"\w+\[([\d,]+)\]", shape):
            if sum(int(d) in wide for d in dims.split(",")) >= 2:
                square.append(line.strip()[:200])
    assert not square, "\n".join(square)


def _expert_streams(text):
    """The shapes the grouped expert products of a compiled program give
    (``[rows of the stream, width]``): which lengths of the stream it
    holds a program for."""
    return {shape.split("{")[0] for _, shape, op, line in
            _hlo_instructions(text)
            if op == "custom-call" and "ragged-dot-none" in line}


def _assert_latent_prefill_kernel(compiled, launches):
    """The cold program's causal pass over the latents is ``launches``
    launches of the one kernel, under the scope its readers look for;
    the fast memory it asks for is the file's stated limit, and what the
    compiler laid out for it lies within."""
    from paddle_tpu.kernels import latent_attention as la
    sizes = lambda key, line: [int(n) for n in re.findall(
        rf'"{key}":\[\{{[^}}]*"size":"(\d+)"', line)]
    calls = [line for _, _, op, line in _hlo_instructions(compiled.as_text())
             if op == "custom-call" and la.PREFILL_KERNEL_NAME in line]
    assert len(calls) == launches, len(calls)
    for line in calls:
        assert "mla_prefill_attn" in line
        asked, = sizes("scoped_memory_configs", line)
        used, = sizes("used_scoped_memory_configs", line)
        assert asked == la._PREFILL_VMEM_BYTES == 64 << 20
        assert 8 << 20 < used <= 32 << 20, used


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


def _ssm_update(layers=36, slots=64, heads=64, head_dim=64, state=128):
    """The decode state update at granite-4.0-h-micro's widths: 36 layers
    of 64 slots of [32, 128, 128] float32, a row's 2 MiB one block."""
    def build(place):
        from paddle_tpu.kernels import ssm_update as su
        pack = su.lane_pack(heads, head_dim)
        return su.ssm_update_pallas, [
            place((layers, slots, heads // pack, state, pack * head_dim),
                  F32), place((), I32),
            place((slots, heads, head_dim), F32), place((slots, heads), F32),
            place((slots, heads), F32), place((slots, state), F32),
            place((slots, state), F32), place((slots,), jnp.bool_)]
    return build


def _engine_decode_hybrid(batch=8, s_max=512, block=16, n_pages=1024):
    """The engine's decode program for a stack of state-space and
    attention layers at granite-4.0-h-micro's widths (three layers of
    it): the paged kernel over heads of 64 packed two to a pool head,
    the state update kernel, both pools and both state arrays donated
    and none of them moved."""
    def build(place):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models.granite_hybrid import (
            GraniteHybridConfig, GraniteHybridForCausalLM)
        paddle.seed(0)
        with _shapes_only():
            model = GraniteHybridForCausalLM(GraniteHybridConfig(
                vocab_size=1024, num_hidden_layers=3,
                layer_types=("mamba", "attention", "mamba"),
                dtype="bfloat16"))
        model.eval()
        eng = DecodeEngine(model, capacity=batch, s_max=s_max,
                           block_size=block, n_blocks=n_pages,
                           prefix_cache=False)
        stacked, *rest = eng._weights()

        def like(a):
            return place(a.shape, a.dtype)

        return eng._decode, [
            jax.tree.map(like, stacked), *jax.tree.map(like, rest), {},
            *(like(jnp.asarray(a))
              for a in (eng._tok, eng._tables, eng._lens)),
            *map(like, eng._pool())]

    def check(compiled):
        text = compiled.as_text()
        assert "ssm_decode_update" in text
        # nothing of a state array's or a pool's size beside the donated
        # buffers themselves
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    build.check = check
    return build


def _engine_mimo(which, slots=48, s_max=9216, n_pages=27649, block=16):
    """The engine's two programs for MiMo-V2.5 at the long_in cell's
    sizes: the published widths, layers 0-6, 16 of the router's 256
    experts held, the vocabulary cut (one matmul behind the stack). The
    model is drawn at debug size and handed over as shapes."""
    def build(place):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models import mimo_v2 as mv
        paddle.seed(0)
        with _shapes_only():
            model = mv.MimoV2ForCausalLM("debug")
        model.eval()
        model.config = full = mv.MimoV2Config(
            vocab_size=1024, num_hidden_layers=7,
            hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
            moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), held_experts=(0, 16),
            dtype="bfloat16")
        eng = DecodeEngine(model, capacity=slots, s_max=s_max,
                           block_size=block, n_blocks=n_pages,
                           prefix_cache=False)
        shapes = mv.leaf_shapes(full)
        leaf = lambda n: place(shapes[n][0], BF16 if shapes[n][1] in
                               ("matrix", "one") else F32)

        def like(a):
            return place(a.shape, a.dtype)

        if which == "prefill":
            fn, data = eng._prefill, [
                place((1, s_max), I32), place((1,), I32),
                place((eng._max_blocks,), I32), place((), I32)]
        else:
            fn, data = eng._decode, [
                like(jnp.asarray(a))
                for a in (eng._tok, eng._tables, eng._lens)]
        return fn, [{n: leaf(n) for n in model._stacked_names()},
                    leaf("embed_tokens"), leaf("final_norm"),
                    leaf("lm_head"), {}, *data, *map(like, eng._pool())]

    def check(compiled):
        text = compiled.as_text()
        # the expert products are the grouped ones, over the pairs routed
        # to held experts: nothing else reads the stack of experts (the
        # conditional that hands it to its branches and their tuples move
        # nothing: _NO_WORK), and nothing has its shape or a [held, rows,
        # width] buffer's
        stacks = ("bf16[96,4096,2048]", "bf16[96,2048,4096]")
        for _, shape, op, line in _hlo_instructions(text):
            assert not any(st in shape for st in stacks), line[:200]
            if any(st in line for st in stacks):
                assert op == "custom-call" and "ragged-dot" in line, \
                    line[:200]
            assert not re.match(r"\w+\[(16|96),\d+,(2048|4096)\]", shape), \
                line[:200]
        # ... over the head of the expert-sorted stream, about as long
        # as an even router's pairs or twice that (P) in an odd number
        # of tiles of 128, beside the whole stream's for a call whose
        # pairs outgrow them: a block of 512 rows (P = 512), a decode
        # step of 48 (P = 128)
        streams = (512 * 8, 640, 384) if which == "prefill" \
            else (slots * 8, 128)
        assert _expert_streams(text) == {
            f"bf16[{r},{width}]" for r in streams
            for width in (2048, 4096)}
        for wide in (256, 128):       # the K pool, the V pool, the rings
            _assert_pools_stay_put(
                compiled, jax.ShapeDtypeStruct(
                    (2, n_pages, 4, block, wide), BF16),
                temp_below=build.temp_below)
        if which == "prefill":
            _assert_no_square_scores(compiled, s_max)
        else:
            assert "paged_decode_qk192" in text
    build.check = check
    # temporaries: 10 MB of the decode step's, 125 MB of a block's (96
    # before the conditional between the short streams and the whole
    # one: its branches' buffers stand side by side), far under a
    # layer's weights or rings
    build.temp_below = (64 << 20) if which == "decode" else (256 << 20)
    return build


def _engine_glm(which, slots=8, s_max=50176, n_pages=25089, block=16):
    """The engine's two programs for GLM-5 at the long_ctx cell's sizes:
    the published widths, layer 0 dense and 5 expert layers, 16 of the
    router's 256 experts held, the vocabulary cut (one matmul behind the
    stack); a latent page of 640 lanes and an indexer-key page of 128
    under one table. The model is drawn at debug size and handed over as
    shapes."""
    def build(place):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models import glm_moe_dsa as gm
        paddle.seed(0)
        with _shapes_only():
            model = gm.GlmMoeDsaForCausalLM("debug")
        model.eval()
        model.config = full = gm.GlmMoeDsaConfig(
            vocab_size=1024, num_hidden_layers=6, first_k_dense_replace=1,
            held_experts=(0, 16), dtype="bfloat16")
        eng = DecodeEngine(model, capacity=slots, s_max=s_max,
                           block_size=block, n_blocks=n_pages,
                           prefix_cache=False)
        shapes = gm.leaf_shapes(full)
        leaf = lambda n: place(shapes[n][0], BF16 if shapes[n][1] in
                               ("matrix", "one", "zero") else F32)

        def like(a):
            return place(a.shape, a.dtype)

        if which == "prefill":
            fn, data = eng._prefill, [
                place((1, s_max), I32), place((1,), I32),
                place((eng._max_blocks,), I32), place((), I32)]
        else:
            fn, data = eng._decode, [
                like(jnp.asarray(a))
                for a in (eng._tok, eng._tables, eng._lens)]
        return fn, [{n: leaf(n) for n in model._stacked_names()},
                    leaf("embed_tokens"), leaf("final_norm"),
                    leaf("lm_head"), {}, *data, *map(like, eng._pool())]

    def check(compiled):
        text = compiled.as_text()
        # the routed experts' products are the grouped ones and nothing
        # has the shape of the stack of experts or of an [held, rows,
        # width] buffer
        stacks = ("bf16[80,6144,2048]", "bf16[80,2048,6144]")
        for _, shape, op, line in _hlo_instructions(text):
            assert not any(st in shape for st in stacks), line[:200]
            if any(st in line for st in stacks):
                assert op == "custom-call" and "ragged-dot" in line, \
                    line[:200]
            assert not re.match(r"\w+\[(16|80),\d+,(2048|6144)\]", shape), \
                line[:200]
            # the indexer's [rows, heads, keys] scores come in pieces
            assert not re.match(rf"\w+\[\d+,32,{s_max}\]", shape), line[:200]
        # a block of 512 rows, a decode step's whole stream of 8 slots
        streams = (512 * 8, 640, 384) if which == "prefill" else (slots * 8,)
        assert _expert_streams(text) == {
            f"bf16[{r},{width}]" for r in streams
            for width in (2048, 6144)}
        for wide in (640, 128):     # the latent pages, the indexer's
            _assert_pools_stay_put(
                compiled, jax.ShapeDtypeStruct(
                    (6, n_pages, 1, block, wide), BF16),
                temp_below=build.temp_below)
        if which == "prefill":
            _assert_no_square_scores(compiled, s_max)
            # dense under ``index_topk`` tokens and masked past it, in
            # the dense layer's loop and in the expert layers'
            _assert_latent_prefill_kernel(compiled, 4)
            return
        # the decode step's selection follows its rows: no value holds
        # every slot's indexer keys to the table's length (a row's own,
        # in the widest way alone, is [pages, block, 128]) or the
        # pool-wide gather of them, nothing under the selection is
        # sorted, and each width of the rule has its way, the row's
        # selection one launch of the kernel in it
        from paddle_tpu.models import glm_moe_dsa as gm
        pages = -(-(s_max + 8) // block)    # the table: a chunk past s_max
        cols = pages * block
        everyone = re.compile(
            rf"\w+\[({slots},{cols}|{slots},{pages},{block}"
            rf"|{slots * pages},{block}),128\]")
        for _, shape, op, line in _hlo_instructions(text):
            assert not everyone.match(shape), line[:200]
            assert not (op == "sort" and ("dsa_topk" in line
                                          or f"[{slots},{cols}]" in line)), \
                line[:200]
        assert "dsa_topk_mask" in text
        for w in gm.decode_widths(cols, 2048, block):
            assert f"s32[{-(-w // 1024) * 8},128]" in text, w
    build.check = check
    # the custom calls are the grouped expert products and a decode
    # row's selection; the decode step reads its pages through the
    # compiler's gathers, no paged kernel
    build.paged_kernel = False
    # temporaries: the cold program's carry of one row's latents and
    # indexer keys (0.46 GB) and a block's scores; the decode step's
    # pages of ONE row to the table's length (64 MB of latents)
    build.temp_below = (128 << 20) if which == "decode" else (2048 << 20)
    return build


def _engine_deepseek(which, slots=16, s_max=33792, n_pages=33793, block=16):
    """The engine's two programs for DeepSeek-V3 at the code_ctx cell's
    sizes: the published widths, layer 0 dense and 4 expert layers, 16 of
    the router's 256 experts held, the vocabulary cut (one matmul behind
    the stack); ONE pool, of latent pages 640 lanes wide. The model is
    drawn at debug size and handed over as shapes."""
    def build(place):
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import DecodeEngine
        from paddle_tpu.models import deepseek_v3 as dv
        from paddle_tpu.models.glm_moe_dsa import leaf_shapes
        paddle.seed(0)
        with _shapes_only():
            model = dv.DeepseekV3ForCausalLM("debug")
        model.eval()
        model.config = full = dv.DeepseekV3Config(
            vocab_size=1024, num_hidden_layers=5, first_k_dense_replace=1,
            held_experts=(0, 16), dtype="bfloat16")
        eng = DecodeEngine(model, capacity=slots, s_max=s_max,
                           block_size=block, n_blocks=n_pages,
                           prefix_cache=False)
        assert len(eng._pool()) == 2        # the pages and the counters
        shapes = leaf_shapes(full, indexer=False)
        leaf = lambda n: place(shapes[n][0], BF16 if shapes[n][1] in
                               ("matrix", "one", "zero") else F32)

        def like(a):
            return place(a.shape, a.dtype)

        if which == "prefill":
            fn, data = eng._prefill, [
                place((1, s_max), I32), place((1,), I32),
                place((eng._max_blocks,), I32), place((), I32)]
        else:
            fn, data = eng._decode, [
                like(jnp.asarray(a))
                for a in (eng._tok, eng._tables, eng._lens)]
        return fn, [{n: leaf(n) for n in model._stacked_names()},
                    leaf("embed_tokens"), leaf("final_norm"),
                    leaf("lm_head"), {}, *data, *map(like, eng._pool())]

    def check(compiled):
        text = compiled.as_text()
        # the routed experts' products are the grouped ones and nothing
        # has the shape of the stack of experts or of an [held, rows,
        # width] buffer
        stacks = ("bf16[64,7168,2048]", "bf16[64,2048,7168]")
        for _, shape, op, line in _hlo_instructions(text):
            assert not any(st in shape for st in stacks), line[:200]
            if any(st in line for st in stacks):
                assert op == "custom-call" and "ragged-dot" in line, \
                    line[:200]
            # ([16, 8, 7168] is the 16 slots' 8 choices, not 16 experts')
            assert not re.match(r"\w+\[(16,(?!8,)|64,)\d+,(2048|7168)\]",
                                shape), line[:200]
        # a block of 512 rows, a decode step's whole stream of 16 slots
        streams = (512 * 8, 640, 384) if which == "prefill" else (slots * 8,)
        assert _expert_streams(text) == {
            f"bf16[{r},{width}]" for r in streams
            for width in (2048, 7168)}
        _assert_pools_stay_put(
            compiled, jax.ShapeDtypeStruct((5, n_pages, 1, block, 640), BF16),
            temp_below=build.temp_below)
        if which == "prefill":
            _assert_no_square_scores(compiled, s_max)
            # in the dense layer's loop and in the expert layers'
            _assert_latent_prefill_kernel(compiled, 2)
            return
        # the decode step reads its latents by the kernel: no value holds
        # a row's pages to the table's length, let alone every slot's
        assert "mla_latent_decode" in text
        pages = -(-(s_max + 8) // block)
        gathered = re.compile(rf"\w+\[(\d+,)?({pages * block}|{pages},{block})"
                              rf",640\]")
        for _, shape, op, line in _hlo_instructions(text):
            assert not gathered.match(shape), line[:200]
    build.check = check
    build.paged_kernel = False      # latent_attention.py's, not paged_attention's
    # temporaries: the cold program's carry of one row's latents (0.22
    # GB) and a piece of a block's scores (128 heads x 256 x 1024
    # float32, 0.13 GB, a few alive); the decode step's are a step's
    build.temp_below = (64 << 20) if which == "decode" else (2048 << 20)
    return build


CASES = {
    "paged_decode_bf16_block16": _paged_decode(16, BF16, 4096),
    # the pool an engine could really hold on 16 GB (2 GiB each of K and
    # V codes here): scalar memory must not grow with it
    "paged_decode_int8_block32_64k_pages": _paged_decode(32, I8, 65536,
                                                         layers=1),
    # the kernel at the doc_qa cell's own sizes (Qwen2-7B: 4 kv heads x
    # 7, 16 rows, tables of 208 pages, 14 layers of 4141 pages)
    "paged_decode_bf16_doc_qa_sizes": _paged_decode(
        16, BF16, 4141, layers=14, batch=16, s_max=3328, kv_heads=4,
        group=7),
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
    "engine_decode_chunk_bf16_block16": _engine_decode("fp", 16),
    "engine_decode_chunk_int8_block32": _engine_decode("int8", 32),
    "engine_decode_chunk_bf16_tp4": _engine_decode("fp", 16, tp=4,
                                                   kv_heads=8),
    "engine_prefill_paged_bf16_chat_sizes": _engine_prefill(),
    # the doc_qa cell's prefix program, the bucket most of its asks take
    "engine_prefill_prefix_bf16_doc_qa_sizes": _engine_prefill(
        s_max=3328, n_pages=4141, batch=16, tail=128),
    "ssm_update_kernel_granite_widths": _ssm_update(),
    "engine_decode_chunk_granite_hybrid": _engine_decode_hybrid(),
    "engine_decode_chunk_mimo_v2_long_in_sizes": _engine_mimo("decode"),
    "engine_prefill_paged_mimo_v2_long_in_sizes": _engine_mimo("prefill"),
    "engine_decode_chunk_glm_moe_dsa_long_ctx_sizes": _engine_glm("decode"),
    "engine_prefill_paged_glm_moe_dsa_long_ctx_sizes": _engine_glm("prefill"),
    "engine_decode_chunk_deepseek_v3_code_ctx_sizes": _engine_deepseek(
        "decode"),
    "engine_prefill_paged_deepseek_v3_code_ctx_sizes": _engine_deepseek(
        "prefill"),
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
    # the engine hands over its program as it jitted it, donation and all
    lower = fn.lower if hasattr(fn, "lower") else jax.jit(fn).lower
    with mock.patch.object(pa, "_pages_per_block",
                           wraps=pa._pages_per_block) as rule:
        t0 = time.perf_counter()
        lowered = lower(*args)
        lower_s = time.perf_counter() - t0
        compiled = lowered.compile()
    # a program that lowers slowly does so in every process that serves
    assert lower_s < getattr(build, "lower_seconds", float("inf")), lower_s
    assert ("tpu_custom_call" in compiled.as_text()) \
        == getattr(build, "kernel", True)
    # every launch of the decode kernel keeps its page buffers (two slots
    # each of K and V, P pages a slot) inside the budget the file states
    assert rule.called == ("decode" in name
                           and getattr(build, "paged_kernel", True))
    for call in rule.call_args_list:
        kvh, bs, hd, dtype, _ = call.args
        pages = pa._pages_per_block(*call.args)
        assert pages >= 1
        assert 4 * pages * kvh * bs * hd * jnp.dtype(dtype).itemsize \
            <= pa._PAGE_BUFFER_BYTES
    if hasattr(build, "check"):
        build.check(compiled)

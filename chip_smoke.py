"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: kernels, serve (fp, int8, chunked), train
    python chip_smoke.py --chips 4   # four chips: the cross-chip paths only

One process, which imports JAX once and starts no other. It drives the
main path through the entry points a user calls (``LlamaForCausalLM``,
``DecodeEngine.submit`` + ``decode_once``, ``model.generate``,
``dist.DistTrainStep``) at the published widths of the ``llama3-8b``
preset — hidden 4096, ff 14336, 32 heads / 8 kv, head_dim 128 — with
depth cut to 2 layers and weights drawn from ``--seed``.

Every line of standard output is one JSON object. The lines before the
last are observations per phase (widths, what was reduced, compile
seconds, cache hits, steps, losses, peak bytes) — never a rate. The last
line is ``{"ok": true, "device": {...}}`` with the device as JAX reports
it. Anything that goes wrong raises, and the exit code is not 0: no
accelerator, a reference path where a kernel belongs, a request that
does not finish, tokens that differ, a loss that does not fall, a second
step that compiles again.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

# -- what runs ---------------------------------------------------------------
LAYERS = 2                  # depth cut (the preset has 32)
TRAIN_VOCAB = 32000         # optimizer state at vocab 128256 is ~21 GB
S_MAX = 2048
# (prompt tokens, max_new); requests 1 and 2 open with the same 1024
# tokens. A finished request publishes its pages to the prefix cache, so
# request 2 is submitted when request 1 is done — beside rows that still
# decode
REQUESTS = [(1536, 16), (1024 + 80, 24), (1024 + 24, 8), (300, 32),
            (45, 12)]
SHARED_PREFIX = 1024
AFTER = {2: 1}              # request 2 waits for request 1
SOLO = (0, 4)               # checked against solo model.generate
# Leading new tokens on which two engines must agree, per request. int8
# KV, the mixed launch and a tp mesh each round differently from the
# one-chip fp decode path, and random weights give flat logits, so a
# greedy chain parts ways at its first near-tie (seen on the chip: int8
# after 1 to 12 tokens, tp=4 after 13 in one request of five). What
# holds the kernels to their arithmetic is kernels_phase.
VARIANT_AGREE = 1           # int8 pools, chunked prefill: the first token
# kernel outputs are O(1) sums of bf16 values: the kernels and the XLA
# references may differ by the rounding of a bf16 MXU pass, not more
KERNEL_ATOL = 2e-2
TP_AGREE = 2                # tp=4: the prefill's token and one decode step's
CHUNKED_REQUESTS = (3, 4)   # the short ones: chunks are one page each
INT8_BLOCK = 32             # one int8 tile of tokens per page
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 6, 2048, 6


class Watch:
    """Counts what JAX reports about compilation between two marks."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as mon
        self.n = {self.LOWER: 0, self.HIT: 0, self.MISS: 0}
        self.compile_s = 0.0
        mon.register_event_listener(self._count)
        mon.register_event_duration_secs_listener(self._duration)

    def _count(self, name, **kw):
        if name in self.n:
            self.n[name] += 1

    def _duration(self, name, secs, **kw):
        self._count(name)
        if name == self.COMPILE:
            self.compile_s += secs

    def mark(self):
        return (self.n[self.LOWER], self.n[self.HIT], self.n[self.MISS],
                self.compile_s)

    def since(self, mark):
        now = self.mark()
        return {"programs_lowered": now[0] - mark[0],
                "cache_hits": now[1] - mark[1],
                "cache_misses": now[2] - mark[2],
                "compile_seconds": round(now[3] - mark[3], 1)}


def emit(**obj):
    print(json.dumps(obj), flush=True)


def peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def device_span(arr):
    """How many devices hold a piece of ``arr``."""
    return len(arr.sharding.device_set)


# -- kernels -----------------------------------------------------------------
KERNEL_LENS = (1, 37, 64, 150)      # ragged rows: tiny, mid-page, on a
#                                     page boundary, many pages


def paged_case(rng, block, dtype, heads=8, group=4, head_dim=128,
               n_pages=40):
    """One small ragged decode input at the real head widths:
    (q, k_pages, v_pages, block_table, seq_lens)."""
    import jax.numpy as jnp
    lens = np.asarray(KERNEL_LENS, np.int32)
    table = np.zeros((lens.size, -(-int(lens.max()) // block)), np.int32)
    free = iter(rng.permutation(np.arange(1, n_pages)))
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // block)):
            table[b, j] = next(free)

    def pool():
        shape = (n_pages, heads, block, head_dim)
        if dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q = jnp.asarray(rng.standard_normal(
        (lens.size, heads, group, head_dim)), jnp.bfloat16)
    return q, pool(), pool(), jnp.asarray(table), jnp.asarray(lens)


def decode_case(case, *scales):
    """``paged_case`` as the decode entry takes it: the pools (and int8
    scales) stacked over two layers, the case's own as layer 1 behind a
    layer of other values, and the layer index after the lengths."""
    import jax.numpy as jnp
    q, k, v, table, lens = case
    k, v, *scales = (jnp.stack([a[::-1], a]) for a in (k, v, *scales))
    return (q, k, v, table, lens, jnp.int32(1), *scales)


def kernels_phase(seed, watch):
    """Each paged kernel against the repo's own XLA reference (the CPU
    tests' oracle) on one small ragged input at the real head widths.
    The entries are called as the model calls them, so on the chip they
    reach the kernels; the references are called by name."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import paged_attention as pa
    mark, t0 = watch.mark(), time.perf_counter()
    rng = np.random.default_rng(seed)
    errs = {}

    def check(name, entry, reference, *args, rows=None):
        if not pa._kernel_serves(args[1]):
            raise AssertionError(f"{name}: the entry would take the "
                                 f"reference path here")
        got = np.asarray(jax.jit(entry)(*args), np.float32)
        want = np.asarray(jax.jit(reference)(*args), np.float32)
        if rows is not None:         # padding query slots are garbage
            got, want = got[rows], want[rows]
        if got.shape != want.shape or not np.isfinite(got).all():
            raise AssertionError(f"{name}: shape {got.shape} vs "
                                 f"{want.shape}, or not finite")
        errs[name] = float(np.abs(got - want).max())
        if errs[name] > KERNEL_ATOL:
            raise AssertionError(
                f"{name}: kernel differs from the reference by "
                f"{errs[name]} (allowed {KERNEL_ATOL})")

    fp = paged_case(rng, 16, jnp.bfloat16)
    check("paged_decode_bf16_block16", pa.paged_decode_attention,
          pa._paged_attn_reference, *decode_case(fp))
    n_pages, heads = fp[1].shape[:2]
    scales = [jnp.asarray(rng.uniform(0.005, 0.02, (n_pages, heads)),
                          jnp.float32) for _ in range(2)]
    check("paged_decode_int8_block32",
          lambda q, k, v, t, n, l, ks, vs: pa.paged_decode_attention(
              q, k, v, t, n, l, kv_scales=(ks, vs)),
          lambda q, k, v, t, n, l, ks, vs: pa._paged_attn_reference_int8(
              q, k, v, t, n, l, (ks, vs)),
          *decode_case(paged_case(rng, 32, jnp.int8), *scales))
    T = 16
    q_lens = np.asarray([1, 16, 5, 16], np.int32)
    q = jnp.asarray(rng.standard_normal(
        (q_lens.size, T, *fp[0].shape[1:])), jnp.bfloat16)
    check("mixed_bf16_block16_window16", pa.mixed_paged_attention,
          pa._mixed_attn_reference, q, *fp[1:], jnp.asarray(q_lens),
          rows=np.arange(T)[None, :] < q_lens[:, None])
    emit(phase="kernels", q_shape=list(fp[0].shape),
         pool_shape=list(fp[1].shape), lens=list(KERNEL_LENS),
         max_abs_err_vs_reference=errs, allowed=KERNEL_ATOL,
         seconds=round(time.perf_counter() - t0, 1), **watch.since(mark))


# -- serve -------------------------------------------------------------------
def serve_config():
    from paddle_tpu.models.llama import LLAMA_PRESETS, LlamaConfig
    return LlamaConfig(**{**LLAMA_PRESETS["llama3-8b"],
                          "num_hidden_layers": LAYERS,
                          "dtype": "bfloat16"})


def widths_of(cfg):
    return {"hidden": cfg.hidden_size, "ff": cfg.intermediate_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "vocab": cfg.vocab_size,
            "layers": cfg.num_hidden_layers, "dtype": cfg.dtype}


def build_model(cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def make_prompts(seed, vocab):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, n).astype(np.int32)
               for n, _ in REQUESTS]
    prompts[2][:SHARED_PREFIX] = prompts[1][:SHARED_PREFIX]
    return prompts


def lowered_text_of(eng, name):
    """Record the StableHLO of engine program ``name`` as the engine
    itself first calls it (same arguments, nothing guessed)."""
    fn = getattr(eng, name)
    seen = {}

    def spy(*args):
        if "text" not in seen:
            seen["text"] = fn.lower(*args).as_text()
        return fn(*args)

    setattr(eng, name, spy)
    return seen


def require_kernel(seen, program):
    """The program was launched and its lowering holds the Pallas
    kernel: a gather reference in its place is a failure here."""
    if "text" not in seen:
        raise AssertionError(f"{program}: the engine never launched it")
    n = seen["text"].count("tpu_custom_call")
    if n == 0:
        raise AssertionError(
            f"{program}: no tpu_custom_call in the lowered program — a "
            f"reference path was taken where the Pallas kernel belongs")
    return n


def drive(eng, prompts, requests, after=None):
    """Submit, step until idle, return each request's full token array.
    ``after`` maps a request to the one it waits for: it is submitted
    once that one has finished."""
    after = after or {}
    handles = {}
    for i, (_, max_new) in enumerate(requests):
        if i not in after:
            handles[i] = eng.submit(prompts[i], max_new_tokens=max_new)
    eng.admit([])
    steps = 0
    while True:
        eng.decode_once()
        steps += 1
        for i, first in after.items():
            if i not in handles and handles[first].event.is_set():
                handles[i] = eng.submit(prompts[i],
                                        max_new_tokens=requests[i][1])
        eng.admit([])
        if eng.idle() and len(handles) == len(requests):
            break
        if steps > 10000:
            raise AssertionError("engine did not drain in 10000 steps")
    outs = []
    for i, (n, max_new) in enumerate(requests):
        out = np.asarray(handles[i].wait(timeout=1))
        if out.shape != (n + max_new,):
            raise AssertionError(
                f"request {i}: {out.shape} tokens, expected "
                f"{(n + max_new,)}")
        if not np.array_equal(out[:n], prompts[i]):
            raise AssertionError(f"request {i}: prompt not echoed")
        outs.append(out)
    return outs, steps


def agree_len(a, b, n_prompt):
    """Leading new tokens on which two outputs of one request agree."""
    new_a, new_b = a[n_prompt:], b[n_prompt:]
    diff = np.nonzero(new_a != new_b)[0]
    return int(diff[0]) if diff.size else int(new_a.size)


def serve_phase(model, prompts, watch, devices):
    """The paged engine three ways on one device: default (fp pools,
    prefix cache on) against solo ``generate``; int8 pools against the
    fp engine; chunked prefill (the mixed launch) against the fp
    engine."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import DecodeEngine
    from paddle_tpu.inference.sharding import make_tp_mesh

    # fp: the engine a user gets by default
    mark, t0 = watch.mark(), time.perf_counter()
    eng = DecodeEngine(model, capacity=4, s_max=S_MAX)
    decode_seen = lowered_text_of(eng, "_decode")
    fp, steps = drive(eng, prompts, REQUESTS, after=AFTER)
    kernels = require_kernel(decode_seen, "decode_chunk_paged")
    stats = eng.stats()
    if stats["prefix_hit_tokens"] <= 0:
        raise AssertionError("no prefix-cache hit: requests 1 and 2 "
                             "share their first tokens")
    for i in SOLO:
        ref = np.asarray(model.generate(
            paddle.to_tensor(prompts[i][None, :]),
            max_new_tokens=REQUESTS[i][1], temperature=0.0)._value)[0]
        if not np.array_equal(fp[i], ref):
            raise AssertionError(
                f"request {i}: engine tokens {fp[i][-REQUESTS[i][1]:]} "
                f"!= solo generate {ref[-REQUESTS[i][1]:]}")
    emit(phase="serve_fp", widths=widths_of(model.config),
         reduced=[f"num_hidden_layers 32 -> {LAYERS}"],
         block_size=eng.block_size, requests=len(REQUESTS),
         prompt_tokens=[n for n, _ in REQUESTS],
         new_tokens=[m for _, m in REQUESTS], engine_steps=steps,
         prefix_hit_tokens=stats["prefix_hit_tokens"],
         equal_solo_generate=list(SOLO),
         tpu_custom_call_in_decode=kernels,
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))
    del eng

    # int8 pools, one int8 tile of tokens per page
    mark, t0 = watch.mark(), time.perf_counter()
    eng = DecodeEngine(model, capacity=4, s_max=S_MAX, kv_dtype="int8",
                       block_size=INT8_BLOCK)
    decode_seen = lowered_text_of(eng, "_decode")
    q8, steps = drive(eng, prompts, REQUESTS, after=AFTER)
    kernels = require_kernel(decode_seen, "decode_chunk_paged[int8]")
    agree = [agree_len(a, b, n)
             for a, b, (n, _) in zip(q8, fp, REQUESTS)]
    if min(agree) < VARIANT_AGREE:
        raise AssertionError(
            f"int8 KV: leading new tokens agreeing with fp per request "
            f"{agree}, need {VARIANT_AGREE}")
    emit(phase="serve_int8", block_size=eng.block_size,
         requests=len(REQUESTS), engine_steps=steps,
         agree_with_fp=agree, need=VARIANT_AGREE,
         new_tokens=[m for _, m in REQUESTS],
         tpu_custom_call_in_decode=kernels,
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))
    del eng

    # chunked prefill. The one-launch mixed step is what an engine with
    # a mesh runs (serving.py, _decode_once_inner); a mesh of one device
    # takes it on one chip.
    mark, t0 = watch.mark(), time.perf_counter()
    sub = [REQUESTS[i] for i in CHUNKED_REQUESTS]
    sub_prompts = [prompts[i] for i in CHUNKED_REQUESTS]
    eng = DecodeEngine(model, capacity=4, s_max=S_MAX,
                       chunked_prefill=True,
                       mesh=make_tp_mesh(1, devices=devices[:1]))
    mixed_seen = lowered_text_of(eng, "_mixed")
    ch, steps = drive(eng, sub_prompts, sub)
    kernels = require_kernel(mixed_seen, "mixed_step")
    agree = [agree_len(a, fp[i], REQUESTS[i][0])
             for a, i in zip(ch, CHUNKED_REQUESTS)]
    if min(agree) < VARIANT_AGREE:
        raise AssertionError(
            f"chunked prefill: leading new tokens agreeing with the fp "
            f"engine per request {agree}, need {VARIANT_AGREE}")
    emit(phase="serve_chunked", requests=len(sub), engine_steps=steps,
         prefill_chunk=eng.prefill_chunk,
         prefill_chunks=eng.stats().get("prefill_chunks"),
         agree_with_fp=agree, need=VARIANT_AGREE,
         new_tokens=[m for _, m in sub],
         tpu_custom_call_in_mixed=kernels,
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))


# -- train -------------------------------------------------------------------
def train_config():
    from paddle_tpu.models.llama import LLAMA_PRESETS, LlamaConfig
    return LlamaConfig(**{**LLAMA_PRESETS["llama3-8b"],
                          "vocab_size": TRAIN_VOCAB,
                          "num_hidden_layers": LAYERS,
                          "max_position_embeddings": 4096,
                          "dtype": "bfloat16", "recompute": True,
                          "recompute_granularity": "core_attn"})


def train_batches(seed, n, batch, seq, vocab):
    """bench.py's structured batches: a noisy affine next-token process
    over a small support, so the loss falls within a few steps and a
    causality or optimizer fault shows as a flat one."""
    rng = np.random.default_rng(seed)
    support = min(256, vocab)
    out = []
    for _ in range(n):
        toks = np.empty((batch, seq), dtype=np.int32)
        toks[:, 0] = rng.integers(0, support, batch)
        noise = rng.integers(-2, 3, size=(batch, seq - 1))
        for t in range(1, seq):
            toks[:, t] = (toks[:, t - 1] * 5 + 17 + noise[:, t - 1]) \
                % support
        out.append(toks)
    return out


def train_steps(cfg, seed, batches, mesh_shape, mesh_names, watch):
    """Build model + AdamW (fp32 master weights) + ``DistTrainStep`` on
    the given mesh and take one step per batch. Returns the losses, the
    model (for its shardings) and the count of programs lowered by the
    steps after the first."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_loss_fn
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)
    mesh = dist.ProcessMesh(shape=list(mesh_shape),
                            dim_names=list(mesh_names))
    dist.shard_model_state(model, mesh)
    step = dist.DistTrainStep(model, opt, llama_loss_fn, mesh, donate=True)
    losses, later = [], None
    for i, toks in enumerate(batches):
        x = paddle.to_tensor(toks)
        losses.append(float(step(x, x)))
        if i == 0:
            later = watch.mark()
    relowered = watch.since(later)["programs_lowered"]
    return losses, model, relowered


def train_phase(seed, watch, devices):
    cfg = train_config()
    mark, t0 = watch.mark(), time.perf_counter()
    batches = train_batches(seed, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ,
                            cfg.vocab_size)
    losses, model, relowered = train_steps(cfg, seed, batches, [1],
                                           ["dp"], watch)
    n_params = sum(p.size for p in model.parameters())
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    if relowered:
        raise AssertionError(
            f"train: {relowered} program(s) lowered after the first "
            f"step — the step recompiled")
    emit(phase="train",
         widths=widths_of(cfg),
         reduced=[f"num_hidden_layers 32 -> {cfg.num_hidden_layers}",
                  f"vocab_size 128256 -> {cfg.vocab_size} (optimizer "
                  f"state at the full vocabulary is ~21 GB, the chip "
                  f"has 16)"],
         precision="bf16 compute, fp32 master weights + AdamW moments",
         params=int(n_params), batch=TRAIN_BATCH, seq=TRAIN_SEQ,
         steps=len(losses), loss_first=round(losses[0], 4),
         loss_last=round(losses[-1], 4),
         programs_lowered_after_first_step=relowered,
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))


# -- four chips --------------------------------------------------------------
def four_chip_phases(seed, watch, devices):
    """Only what exists across chips, each beside its one-chip control:
    the tp=4 engine against the unsharded engine, and the dp2 x mp2
    train step against the one-chip step on the same batches."""
    from paddle_tpu.inference.serving import DecodeEngine
    from paddle_tpu.inference.sharding import make_tp_mesh

    # (a) serve. Attention sums nothing across kv heads, so the decode
    # kernel under a tp=4 shard_map (2 kv heads a chip) must give bit
    # for bit what it gives on one chip.
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.kernels import paged_attention as pa
    mark, t0 = watch.mark(), time.perf_counter()
    mesh = make_tp_mesh(4, devices=devices)
    case = decode_case(
        paged_case(np.random.default_rng(seed), 16, jnp.bfloat16))
    heads, pools = P(None, "tp", None, None), P(None, None, "tp", None, None)
    on_one = jax.jit(pa.paged_decode_attention)(*case)
    on_four = jax.jit(jax.shard_map(
        pa.paged_decode_attention, mesh=mesh,
        in_specs=(heads, pools, pools, P(), P(), P()),
        out_specs=heads))(*case)
    if device_span(on_four) != 4 or not np.array_equal(
            np.asarray(on_one), np.asarray(on_four)):
        raise AssertionError(
            "paged decode kernel under tp=4 shard_map differs from the "
            "one-chip kernel")

    # The engines cannot agree for ever in bf16: each shard rounds its
    # partial wo / w_down product before the psum, one chip rounds the
    # whole sum once, and random weights give near-ties. The first new
    # token crosses the tp prefill, the second the tp decode step.
    model = build_model(serve_config(), seed)
    prompts = make_prompts(seed, model.config.vocab_size)
    one = DecodeEngine(model, capacity=4, s_max=S_MAX)
    ref, _ = drive(one, prompts, REQUESTS, after=AFTER)
    del one
    eng = DecodeEngine(model, capacity=4, s_max=S_MAX, mesh=mesh)
    decode_seen = lowered_text_of(eng, "_decode")
    out, steps = drive(eng, prompts, REQUESTS, after=AFTER)
    kernels = require_kernel(decode_seen, "decode_chunk_paged[tp=4]")
    agree = [agree_len(a, b, n) for a, b, (n, _) in zip(out, ref, REQUESTS)]
    if min(agree) < TP_AGREE:
        raise AssertionError(
            f"tp=4: leading new tokens agreeing with the one-chip engine "
            f"per request {agree}, need {TP_AGREE}")
    st = eng._weights()[0]
    spans = {"wq": device_span(st["wq"]), "wo": device_span(st["wo"]),
             "w_gate": device_span(st["w_gate"]),
             "k_pool": device_span(eng._kp),
             "v_pool": device_span(eng._vp)}
    if min(spans.values()) != 4:
        raise AssertionError(f"tp=4: arrays not on 4 devices: {spans}")
    shard = eng._kp.addressable_shards[0].data.shape
    emit(phase="serve_tp4", requests=len(REQUESTS), engine_steps=steps,
         kernel_under_shard_map_equals_one_chip=True,
         agree_with_one_chip_engine=agree, need=TP_AGREE,
         new_tokens=[m for _, m in REQUESTS], device_set_sizes=spans,
         k_pool_shape=list(eng._kp.shape), k_pool_shard=list(shard),
         tpu_custom_call_in_decode=kernels,
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))
    del eng, model
    gc.collect()

    # (b) train
    mark, t0 = watch.mark(), time.perf_counter()
    cfg = train_config()
    batches = train_batches(seed, 3, TRAIN_BATCH, TRAIN_SEQ,
                            cfg.vocab_size)
    one_losses, model, _ = train_steps(cfg, seed, batches, [1], ["dp"],
                                       watch)
    del model
    gc.collect()
    losses, model, relowered = train_steps(
        cfg, seed, batches, [2, 2], ["dp", "mp"], watch)
    if not np.allclose(losses, one_losses, rtol=2e-2, atol=2e-2):
        raise AssertionError(
            f"dp2 x mp2 losses {losses} != one-chip {one_losses}")
    if relowered:
        raise AssertionError(
            f"dp2 x mp2: {relowered} program(s) lowered after the "
            f"first step")
    params = dict(model.named_parameters())
    spans = {n: device_span(params[n]._value)
             for n in ("wq", "wo", "w_gate", "embed_tokens", "lm_head")}
    if min(spans.values()) != 4:
        raise AssertionError(
            f"dp2 x mp2: parameters not on 4 devices: {spans}")
    emit(phase="train_dp2_mp2", steps=len(losses),
         losses=[round(x, 4) for x in losses],
         one_chip_losses=[round(x, 4) for x in one_losses],
         tolerance="rtol 2e-2, atol 2e-2 (bf16)",
         device_set_sizes=spans,
         wq_shard=list(params["wq"]._value.addressable_shards[0]
                       .data.shape),
         seconds=round(time.perf_counter() - t0, 1),
         peak_bytes=peak_bytes(devices), **watch.since(mark))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    devices = devices[:args.chips]
    watch = Watch()
    emit(phase="start", chips=args.chips, seed=args.seed,
         jax=jax.__version__, compile_cache_dir=cache_dir)

    if args.chips == 4:
        four_chip_phases(args.seed, watch, devices)
    else:
        kernels_phase(args.seed, watch)
        model = build_model(serve_config(), args.seed)
        prompts = make_prompts(args.seed, model.config.vocab_size)
        serve_phase(model, prompts, watch, devices)
        del model
        gc.collect()
        train_phase(args.seed, watch, devices)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

"""One block of 256 queries of the cold prefill's causal pass over the
latents, one layer, at the published widths: the plain pass
(``glm_moe_dsa._causal_latent_pass``'s ``jnp`` body, pieces of 1024 keys
at 128 heads and 2048 at 64) beside the kernel
(``kernels/latent_attention.py``, ``mla_latent_prefill``) at the tile
sizes named on the command line, ``rows,keys`` each (rows a program,
keys a fold):

    python3 log/p45/bench_prefill.py 2048,512 2048,1024 ...

H = 128 dense (a window of 33792, DeepSeek-V3's cell) at 8 k and 32 k of
context and over a 5 k prompt's 20 blocks; H = 64 under a mask of 2048
allowed keys a query (a window of 50176, GLM-5's cell) at 8 k and 32 k.
PERF.md, Findings PR 45."""
import json
import os
import sys
import time
from types import SimpleNamespace
from unittest import mock

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from paddle_tpu.kernels import latent_attention as LA
from paddle_tpu.models import glm_moe_dsa as G

TINY = os.environ.get("BENCH_TINY") == "1"      # the CPU rehearsal
blk, lanes, rank = (16, 128, 64) if TINY else (256, 640, 512)
WINDOWS = (96, 96) if TINY else (33792, 50176)
CTXS = (48, 96) if TINY else (8192, 32768)
cfg = SimpleNamespace(kv_lora_rank=rank, logit_divisor=1.0 / 0.1352)
FLOP_PAIR = lambda H: 2 * H * (576 + 512)       # the absorbed form's own


def inputs(H, total):
    lat = jax.random.normal(jax.random.key(0), (1, total, lanes),
                            jnp.bfloat16).at[..., lanes * 9 // 10:].set(0)
    qc = (jax.random.normal(jax.random.key(1), (blk, H, lanes), jnp.bfloat16)
          * 0.05).at[..., lanes * 9 // 10:].set(0)
    return qc, lat


def allowed_mask(total, k=32 if TINY else 2048):
    """[blk, total] bool, ``k`` columns a query drawn over the window's
    first 32 k (the columns a query at 32 k may see)."""
    sc = jax.random.uniform(jax.random.key(2), (blk, total))
    sc = jnp.where(jnp.arange(total)[None] < CTXS[1], sc, -jnp.inf)
    return G._chosen_mask(sc, k)


def plain(at_most):
    def f(qc, lat, start, pad, *allowed):
        with mock.patch.object(jax, "default_backend", lambda: "cpu"):
            return G._causal_latent_pass(
                cfg, qc, lat, 0, start, start + jnp.arange(blk), pad,
                pad // blk, *allowed, at_most=at_most)
    return jax.jit(f)


def kernel():
    def f(qc, lat, start, pad, *allowed):
        return LA.latent_prefill_pallas(qc, lat, 0, start, pad, *allowed,
                                        rank=rank, scale=0.1352,
                                        interpret=TINY)
    return jax.jit(f)


def timed(fn, *args, n=5):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        o = fn(*args)
    o.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e3


def prompt(fn, qc, lat, tokens=80 if TINY else 5120):
    """ms of the blocks of one prompt of ``tokens`` right-aligned in the
    window, summed."""
    total = lat.shape[1]
    starts = [jnp.int32(s) for s in range(total - tokens, total, blk)]
    pad = jnp.int32(total - tokens)
    for s in starts[:1]:
        fn(qc, lat, s, pad).block_until_ready()
    t0 = time.perf_counter()
    for s in starts:
        o = fn(qc, lat, s, pad)
    o.block_until_ready()
    return (time.perf_counter() - t0) * 1e3


def measure(label, fn, H, total, masked, want=None):
    qc, lat = inputs(H, total)
    extra = (allowed_mask(total),) if masked else ()
    row = {}
    for ctx in CTXS:
        start, pad = jnp.int32(ctx - blk), jnp.int32(0)
        pairs = blk * (ctx - blk + (blk + 1) / 2)
        ms = timed(fn, qc, lat, start, pad, *extra)
        row[f"ms@{ctx}"] = round(ms, 3)
        row[f"own_tflops@{ctx}"] = round(pairs * FLOP_PAIR(H) / ms / 1e9, 1)
        row[f"ms_per_1024_keys@{ctx}"] = round(ms / (ctx / 1024), 4)
    if not masked:
        row["ms_5k_prompt"] = round(prompt(fn, qc, lat), 3)
    got = fn(qc, lat, jnp.int32(CTXS[0] - blk), jnp.int32(CTXS[0] // 100 + 5),
             *extra)
    if want is not None:
        row["max_abs_diff"] = float(jnp.abs(got - want).max())
        row["width"] = float(jnp.abs(want).max())
    print(label, json.dumps(row), flush=True)
    return row, got


out = {}
want = {}
for H, total, masked, at_most in ((128, WINDOWS[0], False, 1024),
                                  (64, WINDOWS[1], True, 2048)):
    key = f"H{H}{'_masked' if masked else ''}"
    out[f"plain_{key}"], want[key] = measure(f"plain_{key}", plain(at_most),
                                             H, total, masked)
for spec in sys.argv[1:]:
    rows, keys = map(int, spec.split(",")[:2])
    for H, total, masked in ((128, WINDOWS[0], False),
                             (64, WINDOWS[1], True)):
        key = f"H{H}{'_masked' if masked else ''}"
        label = f"kernel_{rows}_{keys}_{key}"
        with mock.patch.multiple(LA, _PREFILL_ROWS=rows, _PREFILL_KEYS=keys):
            try:
                t0 = time.perf_counter()
                out[label], _ = measure(label, kernel(), H, total, masked,
                                        want[key])
                out[label]["all_s"] = round(time.perf_counter() - t0, 1)
            except Exception as e:  # noqa: BLE001 - a size the chip refuses
                out[label] = {"error": repr(e)[:400]}
                print(label, json.dumps(out[label]), flush=True)
os.makedirs("chiprun_out/p45", exist_ok=True)
json.dump(out, open("chiprun_out/p45/bench_prefill.json", "w"), indent=1)

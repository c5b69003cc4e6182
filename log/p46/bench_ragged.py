"""(a) One grouped product (``lax.ragged_dot``, precision DEFAULT, the
chip's grouped kernel) at each expert-share family's widths, over the
stack the family's program holds (16 held experts x its expert layers),
the 16 experts of one layer visited, at streams of 128 to 640 rows with 8
and with 16 rows an expert; the up direction ([m, d] x [E, d, 2048], two
of an expert FFN's three products) and the down direction ([m, 2048] x
[E, 2048, d]); ms a call, and the three products of one layer's FFN.

    python3 log/p46/bench_ragged.py

PERF.md, Findings PR 46."""
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

TINY = os.environ.get("BENCH_TINY") == "1"      # the CPU rehearsal
F = 64 if TINY else 2048
FAMILIES = {"mimo_v2": (4096, 6), "glm_moe_dsa": (6144, 5),
            "deepseek_v3": (7168, 4)}
STREAMS = (128, 256, 384, 512, 640)
HELD = 16


def timed(fn, *args, n=20):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        o = fn(*args)
    o.block_until_ready()
    return (time.perf_counter() - t0) / n * 1e3


@jax.jit
def product(stream, stack, sizes):
    return jax.lax.ragged_dot(stream, stack, sizes,
                              precision=jax.lax.Precision.DEFAULT)


out = {}
for family, (d, layers) in FAMILIES.items():
    if TINY:
        d //= 64
    n = HELD * layers
    up = jax.random.normal(jax.random.key(0), (n, d, F), jnp.bfloat16)
    down = jax.random.normal(jax.random.key(1), (n, F, d), jnp.bfloat16)
    for per in (8, 16):
        # the second of the stack's layers: its groups lie behind another's
        sizes = jnp.zeros((n,), jnp.int32).at[HELD:2 * HELD].set(per)
        for m in STREAMS:
            if m < HELD * per:
                continue
            x = jax.random.normal(jax.random.key(2), (m, d), jnp.bfloat16)
            h = jax.random.normal(jax.random.key(3), (m, F), jnp.bfloat16)
            row = {"up_ms": round(timed(product, x, up, sizes), 4),
                   "down_ms": round(timed(product, h, down, sizes), 4)}
            row["ffn_ms"] = round(2 * row["up_ms"] + row["down_ms"], 4)
            # the least: the visited experts' weights read once
            row["bytes_floor_ms"] = round(
                HELD * 3 * d * F * 2 / 819e9 * 1e3, 4)
            out[f"{family}_rows{per}_stream{m}"] = row
            print(family, per, m, json.dumps(row), flush=True)
    del up, down
os.makedirs("chiprun_out/p46", exist_ok=True)
json.dump(out, open("chiprun_out/p46/bench_ragged.json", "w"), indent=1)

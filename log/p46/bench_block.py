"""(b) One cold prefill of a cell's median prompt (and of half of it)
through the engine's own program, at blocks of 256 and of 512 rows, each
with the parent's rungs of the expert stream (P / 2, P) and this PR's
(odd multiples of 128 rows); (c) the pairs a block brings a held share
of the experts: mean and standard deviation over (block, expert layer),
and what share of them took which rung.

    python3 log/p46/bench_block.py <cell> [--manifest <file>] [--blocks 256,512]

The model, its weights and the engine's sizes are the cell's
(``benchmark/lib``'s builder, the mix's ``engine``); the program is
launched as ``DecodeEngine._prefill_row_inner`` launches it. PERF.md,
Findings PR 46."""
import argparse
import gc
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, ".")
import numpy as np

ap = argparse.ArgumentParser()
ap.add_argument("cell")
ap.add_argument("--manifest")
ap.add_argument("--blocks", default="256,512")
ap.add_argument("--seed", type=int, default=4600000101)
ap.add_argument("--reps", type=int, default=3)
ap.add_argument("--pairs", type=int, default=0)     # (c) too
args = ap.parse_args()

import jax

from benchmark.lib import manifest as mf
from benchmark.runners.serve import engine_kwargs
from paddle_tpu.distributed.fleet import moe
from paddle_tpu.inference.serving import DecodeEngine
from paddle_tpu.models import paged_stack

manifest = mf.load_manifest(args.manifest)
cfg, mix = mf.cell_files(manifest, mf.find_cell(manifest, args.cell))
builder, _ = mf.serve_modules(cfg)
model = builder.build_model(cfg, args.seed)
capacity, kw = engine_kwargs(cfg, mix, False, builder)
median = int(mix["prompt"]["median"])


def parents_rungs(n_pairs, stream_rows):
    if stream_rows is None or 2 * stream_rows > n_pairs:
        return ()
    return tuple(r for r in (stream_rows // 2, stream_rows) if r % 128 == 0)


def squares(sizes, n_pairs, stream_rows=None):
    """In the place of ``moe_stream_rows``: the last device counter then
    adds up the squares of the pairs a (block, layer) brought."""
    return sizes.sum() ** 2


def prefill(eng, tokens):
    """One launch of the cold program over ``tokens``, as
    ``_prefill_row_inner`` makes it; seconds until its token is read."""
    ns, bs = tokens.size, eng.block_size
    ids = np.full((1, eng.s_max), eng.pad_id, np.int32)
    ids[0, eng.s_max - ns:] = tokens
    table_row = np.zeros((eng._max_blocks,), np.int32)
    pages = -(-ns // bs)
    table_row[:pages] = 1 + np.arange(pages)
    st, embed, fnorm, lm = eng._weights()
    t0 = time.perf_counter()
    first, *pool = eng._prefill(
        st, embed, fnorm, lm, eng._scales, ids,
        np.array([eng.s_max - ns], np.int32), table_row, np.int32(0),
        *eng._pool())
    eng._set_pool(pool)
    np.asarray(first)
    return time.perf_counter() - t0


def variant(block, rungs, lengths, counter=None):
    patches = [mock.patch.object(paged_stack, "prefill_block_rows",
                                 lambda cfg, s_max: block)]
    if rungs == "parent":
        patches.append(mock.patch.object(moe, "moe_stream_rungs",
                                         parents_rungs))
    if counter is not None:     # deepseek_v3 runs glm_moe_dsa's _ffn
        from paddle_tpu.models import glm_moe_dsa, mimo_v2
        patches += [mock.patch.object(family, "moe_stream_rows", counter)
                    for family in (glm_moe_dsa, mimo_v2)]
    for p in patches:
        p.start()
    try:
        eng = DecodeEngine(model, capacity=capacity, **kw)
        assert eng._prefill_block == block
        row = {"block": block, "rungs": rungs}
        for n in lengths:
            tokens = np.random.RandomState(args.seed % (2 ** 31) + n).randint(
                0, cfg["vocab_size"], (n,)).astype(np.int32)
            t_first = prefill(eng, tokens)
            before = eng.stats()
            ts = [prefill(eng, tokens) for _ in range(args.reps)]
            after = eng.stats()
            grown = {k: (after[k] - before[k]) // args.reps
                     for k in ("moe_pairs", "moe_expert_visits",
                               "moe_full_stream", "moe_stream_rows")}
            row[str(n)] = {"s": round(min(ts), 5),
                           "s_all": [round(t, 5) for t in ts],
                           "us_a_token": round(min(ts) / n * 1e6, 3),
                           "first_call_s": round(t_first, 2), **grown}
        return row
    finally:
        for p in patches:
            p.stop()
        del eng
        gc.collect()


out = {"cell": args.cell, "device": jax.devices()[0].device_kind}
os.makedirs("chiprun_out/p46", exist_ok=True)
lengths = (median, median // 2)
for block in map(int, args.blocks.split(",")):
    for rungs in ("parent", "odd"):
        row = variant(block, rungs, lengths)
        out[f"block{block}_{rungs}"] = row
        print(json.dumps(row), flush=True)
json.dump(out, open(f"chiprun_out/p46/bench_block_{args.cell}.json", "w"),
          indent=1)
if not args.pairs:
    sys.exit(0)
# (c) at the last block size, this PR's rungs: whole blocks only, so that
# every (block, layer) holds `block` real tokens
whole = [max(block, n // block * block) for n in (median, median // 2,
                                                  median * 3 // 2)]
plain = variant(block, "odd", whole)
sq = variant(block, "odd", whole, counter=squares)
c = model.config
moe_layers = sum(c.moe_layer_freq) if hasattr(c, "hybrid_layer_pattern") \
    else c.num_hidden_layers - c.first_k_dense_replace
hist = {"block": block}
for n in whole:
    a, b = plain[str(n)], sq[str(n)]
    units = (n // block) * moe_layers
    hist[str(n)] = {"pairs": a["moe_pairs"], "sum_of_squares":
                    b["moe_stream_rows"], "stream_rows": a["moe_stream_rows"],
                    "full_stream": a["moe_full_stream"],
                    "block_layers": units}
    mean = a["moe_pairs"] / units
    var = b["moe_stream_rows"] / units - mean ** 2
    hist[str(n)].update(mean_pairs=round(mean, 2),
                        std_pairs=round(max(var, 0) ** 0.5, 2))
out["pairs_a_block"] = hist
print(json.dumps(hist), flush=True)
json.dump(out, open(f"chiprun_out/p46/bench_block_{args.cell}.json", "w"),
          indent=1)

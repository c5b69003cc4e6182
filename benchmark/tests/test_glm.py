"""The glm-5-serve configuration's own pieces: its file against the
catalog's numbers and the issue's arithmetic, the bytes its builder
counts, the reference's selection and routing against slower spellings of
them, the two controls in the form the limits take, the two counts
against hand counts, and the four readers on made-up launches and on a
recorded slice of a v5e trace."""

import json
import pathlib

import numpy as np
import pytest

from benchmark.kernels import dsa_decode, dsa_prefill
from benchmark.lib import dsa_span, glm_program, glm_reference, layer_metrics
from benchmark.lib import glm_weights as W
from benchmark.lib import manifest as mf
from benchmark.lib import trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = mf.load_json(ROOT / "benchmark/configs/glm-5-serve.json")
DEBUG = mf.load_json(ROOT / "benchmark/tests/rehearsal/debug-glm.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
SLICE = ROOT / "benchmark/tests/data/trace_slice_glm_v5e.json"

# the reference's blocks at the debug sizes of these tests
glm_reference.SEQ_BUCKET, glm_reference.Q_BLOCK = 32, 16
glm_reference.I_BLOCK, glm_reference.T_BLOCK = 8, 16
glm_reference.HEAD_GROUP = 2


def test_the_file_holds_the_published_widths_and_states_its_share():
    z = W.sizes(CFG)
    assert (z["d"], z["h"], z["qr"], z["rank"]) == (6144, 64, 2048, 512)
    assert (z["nope"], z["rope"], z["hdv"]) == (192, 64, 256)
    assert (z["hi"], z["di"], z["topk"]) == (32, 128, 2048)
    assert (z["ff"], z["fe"], z["fs"], z["top_k"]) == (12288, 2048, 2048, 8)
    assert z["theta"] == 1e6 and CFG["routed_scaling_factor"] == 2.5
    # the share: 16 of the router's 256 experts, an eighth of the vocabulary
    assert (z["experts"], z["held"], z["first"], z["vocab"]) == \
        (256, 16, 0, 19360)
    assert CFG["published"]["n_routed_experts"] == 256
    assert CFG["published"]["vocab_size"] == 154880 == 8 * 19360
    assert W.kinds(CFG) == ["dense"] + ["moe"] * 5
    assert set(CFG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == "glm-5-serve")
    assert set(entry["reduced"]) == set(CFG["reduced"])
    count = lambda leaves: sum(int(np.prod(s)) for s, _ in leaves.values())
    attention = count(W.attention_leaves(CFG))
    # the issue's arithmetic: attention 165.0 M and the indexer 9.4 M a
    # layer (the four norms and the LayerNorm's 256 values beside them)
    assert round(attention / 1e6, 1) == 174.4
    dense = attention + count(W.dense_leaves(CFG))
    moe = attention + count(W.moe_leaves(CFG)) + 16 * count(W.expert_leaves(CFG))
    assert round(dense / 1e6, 1) == 400.9 and round(moe / 1e6, 1) == 817.7
    held = dense + 5 * moe + count(W.top_leaves(CFG))
    assert round(held / 1e9, 2) == 4.73
    whole = 3 * dense + 75 * (moe + 240 * count(W.expert_leaves(CFG))) \
        + 2 * 154880 * 6144
    assert round(whole / 1e9) == 744
    # a block of 16 tokens: 6 layers x (640 + 128) lanes x 2 B
    assert glm_program.kv_bytes_per_block(CFG, 16) == 147456
    mix = mf.load_json(ROOT / "benchmark/traffic/long_ctx.json")
    slots, s_max = mix["engine"]["capacity"], mix["engine"]["s_max"]
    assert mix["engine"]["kv_pool_bytes"] // 147456 == slots * s_max // 16 + 1
    builder, reference = mf.serve_modules(CFG)
    assert builder is glm_program and reference is glm_reference
    full = glm_program.glm_config(CFG)
    assert (full.latent_lanes, full.held_experts, full.rope_theta) == \
        (640, (0, 16), 1e6)
    # drawn so wide that both logits have a spread of LOGIT_STD
    wide = W._wide(CFG)
    assert 0.06 < wide["w_uq"] < 0.066 and 0.058 < wide["w_qi"] < 0.063


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog on this host")
def test_every_number_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CFG["reduced"] or CFG[key] == value, key
    for key in CFG["reduced"]:
        assert CFG["published"][key] == row["config"][key], key


def _layer(kind="moe", layer=1):
    import jax.numpy as jnp
    return {k: v.astype(jnp.float32) for k, v in W.make_layer(
        W.seed_key(3), DEBUG, layer, kind, jnp.bfloat16).items()}


def test_the_references_selection_and_routing_against_slower_spellings():
    """Token by token with numpy: the indexer's scores head by head, the
    chosen set by a stable sort; sigmoid scores, the top-k of scores plus
    bias by a sort, weights the chosen scores over their sum, times the
    scaling factor."""
    import jax
    z, lp = W.sizes(DEBUG), _layer()
    n = jax.random.normal(jax.random.key(0), (48, z["d"]))
    c_q = glm_reference._rms(n @ lp["w_dq"], lp["q_ln"], 1e-5)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(glm_reference.allowed_keys(DEBUG, lp, n, c_q))
        qi, ki, w = (np.asarray(a, np.float64)
                     for a in glm_reference.index_scores(DEBUG, lp, n, c_q))
    for t in range(48):
        score = sum(w[t, j] * np.maximum(ki[:t + 1] @ qi[t, j], 0.0)
                    for j in range(z["hi"]))
        chosen = np.argsort(-score, kind="stable")[:z["topk"]]
        assert set(np.flatnonzero(got[t])) == set(chosen), t
    recent = np.asarray(glm_reference.allowed_keys(DEBUG, lp, n, c_q,
                                                   "recent"))
    assert (np.flatnonzero(recent[40]) == np.arange(33, 41)).all()
    got = np.asarray(glm_reference.route(DEBUG, lp["router"],
                                         lp["router_bias"], n))
    router = np.asarray(lp["router"], np.float64)
    bias = np.asarray(lp["router_bias"], np.float64)
    want = np.zeros_like(got, dtype=np.float64)
    for t, row in enumerate(np.asarray(n, np.float64)):
        scores = 1.0 / (1.0 + np.exp(-(row @ router)))
        chosen = np.argsort(-(scores + bias), kind="stable")[:z["top_k"]]
        want[t, chosen] = 2.5 * scores[chosen] / scores[chosen].sum()
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_controls_are_the_reference_lower_and_the_reference_wrong():
    import jax.numpy as jnp
    tokens = np.random.default_rng(0).integers(1, DEBUG["vocab_size"], 40)
    positions = np.arange(40)
    ref = np.asarray(glm_reference.logits_of(1, DEBUG, tokens, positions))
    low = np.asarray(glm_reference.logits_of(1, DEBUG, tokens, positions,
                                             precision="int8"))
    wrong = np.asarray(glm_reference.logits_of(1, DEBUG, tokens, positions,
                                               selection="recent"))
    assert ref.shape == low.shape == (40, DEBUG["vocab_size"])
    # under index_topk every earlier token is allowed whatever the
    # precision; past it a rounding may choose other tokens
    topk = DEBUG["index_topk"]
    assert 0 < np.abs(ref - low)[:topk].max() < ref.std()
    assert np.abs(ref - low).max() < 4 * ref.std()
    np.testing.assert_allclose(wrong[:topk], ref[:topk], atol=1e-6)
    assert np.abs(wrong[topk + 4:] - ref[topk + 4:]).max() > 1e-2
    gaps = glm_reference.served_gaps(1, DEBUG, tokens, 8, control=True)
    assert gaps["served"].shape == gaps["control"].shape == (32,)
    assert (gaps["control"] >= 0).all() and (gaps["served"] >= 0).all()
    assert jnp.isfinite(ref).all()


def test_the_two_counts_against_hand_counts():
    ops, nbytes = dsa_decode.needs(scored_tokens=30000, selected_tokens=2048,
                                   cfg=CFG)
    assert nbytes == 30000 * 128 * 2 + 2048 * 576 * 2
    assert ops == 30000 * 2 * 32 * 128 + 2048 * 64 * 2 * (576 + 512)
    # one row at 30 k: the indexer's 7.7 MB of keys bound it
    assert dsa_decode.least_seconds(30000, 2048, CFG, PEAKS) == \
        pytest.approx(nbytes / 819e9)
    assert dsa_prefill.pairs(100, 2048) == (5050.0, 5050.0)
    scored, attended = dsa_prefill.pairs(10000, 2048)
    assert scored == 10000 * 10001 / 2
    assert attended == 2048 * 2049 / 2 + (10000 - 2048) * 2048
    assert dsa_prefill.operations(scored, attended, CFG) == \
        2 * scored * 32 * 128 + 2 * attended * 64 * 512
    # a prompt's first blocks and its last add up to the whole
    head, whole = dsa_prefill.pairs(6000.5, 2048), dsa_prefill.pairs(10000, 2048)
    assert head[0] < whole[0] and head[1] < whole[1]


def test_dsa_selected_share_divides_the_counters():
    read = layer_metrics.load_reader("dsa_selected_share")
    before = {"dsa_scored_tokens": 1000, "dsa_selected_tokens": 900}
    after = {"dsa_scored_tokens": 101000, "dsa_selected_tokens": 13188}
    assert read({"before": before, "after": after}) == pytest.approx(12.288)
    assert read({"before": before, "after": before}) is None
    assert read({"before": {"device_steps": 1},
                 "after": {"device_steps": 9}}) is None     # the parent
    assert read({}) is None


def _made_up():
    """A span that cuts a prefill at its start, holds a decode chunk of 2
    steps and the first 2 blocks of a prefill its end cuts; 1 expert
    layer of 2, so 3 products a step or block."""
    ev = trace_reduce.Event
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, \
        trace_reduce.MODULES_LINE
    cfg = dict(CFG, num_hidden_layers=2, first_k_dense_replace=1)
    tables = {"jit_decode_chunk_paged": {"fusion.1": "dsa_index_scores",
                                         "sort.2": "dsa_topk",
                                         "fusion.3": "mla_sparse_decode",
                                         "while.9": "dsa_index_scores"},
              "jit_prefill_paged": {"fusion.11": "dsa_index_scores",
                                    "fusion.12": "dsa_topk",
                                    "fusion.13": "mla_prefill_attn",
                                    "fusion.14": "moe_shared_ffn"}}
    product = "ragged-dot-none.5 = bf16[8,2048] custom-call("
    events, t = [], 0.0

    def unit(names):
        nonlocal t
        for name, dur in (*names, *[(product, 10.0)] * 3):
            text = name if " = " in name else f"{name} = f32[8] fusion("
            events.append(ev(dev, ops, "%" + text, t, dur))
            t += dur

    prefill = (("fusion.11", 100.0), ("fusion.12", 50.0), ("fusion.13", 800.0),
               ("fusion.14", 40.0), ("fusion.99", 500.0))
    unit(prefill)                               # the tail: 1 block
    start = t
    for _ in range(2):                          # a decode chunk: 2 steps
        unit((("fusion.1", 60.0), ("sort.2", 50.0), ("fusion.3", 40.0),
              ("fusion.99", 900.0)))
    events.append(ev(dev, ops, "%while.9 = (s32[]) while(", start, t - start))
    events.append(ev(dev, mods, "jit_decode_chunk_paged(7)", start, t - start))
    for _ in range(2):                          # the head: 2 blocks
        unit(prefill)
    log = [[8.0, "prefill", 40, 1, 10240, 0, 0, 0, 1000, 100],
           [8.9, "decode", 8, 3, 90000, 5, 5, 0, 541000, 37864],
           [10.2, "decode", 8, 3, 90096, 9, 9, 0, 1081576, 74728],
           [10.4, "prefill", 30, 1, 7680, 20, 20, 0, 1081576, 74728]]
    return {"trace": trace_reduce.Reduced(events), "peaks": PEAKS, "cfg": cfg,
            "after": {"launches": log, "scopes": tables},
            "trace_span": (10.0, 13.0)}


def test_a_traced_span_is_read_launch_by_launch():
    ctx = _made_up()
    found = dsa_span.segments(ctx)
    assert [(s.kind, s.part, s.units) for s in found] == [
        ("prefill", "tail", 1.0), ("decode", "whole", 2.0),
        ("prefill", "head", 2.0)]
    assert found[0].seconds == {"dsa_index_scores": 1e-7, "dsa_topk": 5e-8,
                                "mla_prefill_attn": 8e-7,
                                "moe_shared_ffn": 4e-8}
    assert found[1].attention_s == pytest.approx(2 * 150e-9)   # no loop
    cfg = ctx["cfg"]
    share = layer_metrics.load_reader("dsa_attn_share")(ctx)
    busy = ctx["trace"].busy_s
    assert share == pytest.approx(100 * (3 * 950e-9 + 300e-9) / busy)
    # decode: 2 steps' worth of the one launch near the span (10.2; the
    # one at 9.5 lies over a second ahead): its counts a step
    got = layer_metrics.load_reader("dsa_decode_roofline")(ctx)
    least = dsa_decode.least_seconds(2 * 540576 / 8, 2 * 36864 / 8, cfg, PEAKS)
    assert got == pytest.approx(100 * least / 300e-9)
    # prefill: the tail's last block of the 10240-token prompt launched
    # before the span, and the first 2 blocks of a prompt
    got = layer_metrics.load_reader("dsa_prefill_roofline")(ctx)
    whole, gone = dsa_prefill.pairs(10240, 2048), dsa_prefill.pairs(9984, 2048)
    head = dsa_prefill.pairs(512, 2048)
    least = dsa_prefill.least_seconds(
        2 * (whole[0] - gone[0] + head[0]),
        2 * (whole[1] - gone[1] + head[1]), cfg, PEAKS)
    assert got == pytest.approx(100 * least / (3 * 950e-9))
    # no entry of a prefill before the span: the tail is left out whole
    later = {**ctx, "after": {**ctx["after"],
                              "launches": ctx["after"]["launches"][1:]}}
    least = dsa_prefill.least_seconds(2 * head[0], 2 * head[1], cfg, PEAKS)
    assert layer_metrics.load_reader("dsa_prefill_roofline")(later) == \
        pytest.approx(100 * least / (2 * 950e-9))
    # nothing to read: the parent's program (no table), another family,
    # no trace, no peaks
    for name in ("dsa_decode_roofline", "dsa_prefill_roofline",
                 "dsa_attn_share"):
        read = layer_metrics.load_reader(name)
        assert read({**ctx, "after": {"launches": ctx["after"]["launches"]}}) \
            is None
        assert read({**ctx, "cfg": {"hidden_size": 3584}}) is None
        assert read({**ctx, "trace": trace_reduce.Reduced([])}) is None
        assert read({}) is None
    assert layer_metrics.load_reader("dsa_decode_roofline")(
        {**ctx, "peaks": None}) is None


@pytest.mark.skipif(not SLICE.exists(), reason="no recorded slice")
def test_the_readers_on_a_recorded_slice():
    """The tail of a cold prefill, one decode chunk and the first blocks
    of a cold prefill of the long_ctx cell as a v5e traced them, read
    with the engine's table and the launches' entries of that run: a
    share is a share, above 0 and under 100."""
    with open(SLICE) as f:
        rec = json.load(f)
    trace = trace_reduce.Reduced([trace_reduce.Event(*e[:5])
                                  for e in rec["events"]])
    ctx = {"trace": trace, "peaks": PEAKS, "cfg": CFG,
           "after": {"launches": rec["launches"], "scopes": rec["scopes"]},
           "trace_span": rec["trace_span"]}
    found = dsa_span.segments(ctx)
    assert {s.kind for s in found} == {"decode", "prefill"}
    for s in found:
        assert s.units > 0 and s.attention_s > 0
    for name in ("dsa_decode_roofline", "dsa_prefill_roofline",
                 "dsa_attn_share"):
        share = layer_metrics.load_reader(name)(ctx)
        assert 0.0 < share < 100.0, name

"""The deepseek-v3-serve configuration's own pieces: its file against the
catalog's numbers and the issue's arithmetic, the bytes its builder
counts (one page, no second pool), that every share draws the same
expert, the reference's routing inside groups against a slower spelling,
the two controls in the form the limits take, the two counts against hand
counts, and the four readers on made-up launches and on a recorded slice
of a v5e trace."""

import json
import pathlib

import numpy as np
import pytest

from benchmark.kernels import mla_decode, mla_prefill
from benchmark.lib import deepseek_program, deepseek_reference, dsa_span
from benchmark.lib import deepseek_weights as W
from benchmark.lib import glm_weights as GW
from benchmark.lib import layer_metrics
from benchmark.lib import manifest as mf
from benchmark.lib import trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = mf.load_json(ROOT / "benchmark/configs/deepseek-v3-serve.json")
DEBUG = mf.load_json(ROOT / "benchmark/tests/rehearsal/debug-deepseek.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
SLICE = ROOT / "benchmark/tests/data/trace_slice_deepseek_v5e.json"
CELL = "deepseek-v3-serve.code_ctx"

# the reference's blocks at the debug sizes of these tests
deepseek_reference.SEQ_BUCKET, deepseek_reference.Q_BLOCK = 64, 16
deepseek_reference.T_BLOCK, deepseek_reference.HEAD_GROUP = 16, 2


def test_the_file_holds_the_published_widths_and_states_its_share():
    z = W.sizes(CFG)
    assert (z["d"], z["h"], z["qr"], z["rank"]) == (7168, 128, 1536, 512)
    assert (z["nope"], z["rope"], z["hdv"]) == (128, 64, 128)
    assert (z["ff"], z["fe"], z["fs"], z["top_k"]) == (18432, 2048, 2048, 8)
    assert (z["n_group"], z["topk_group"]) == (8, 4)
    assert z["theta"] == 1e4 and CFG["routed_scaling_factor"] == 2.5
    assert z["scale"] == pytest.approx(0.07217 * 1.8738, rel=1e-4)
    # the share: 16 of the router's 256 experts, an eighth of the vocabulary
    assert (z["experts"], z["held"], z["first"], z["vocab"]) == \
        (256, 16, 0, 16160)
    assert CFG["published"]["vocab_size"] == 129280 == 8 * 16160
    assert W.kinds(CFG) == ["dense"] + ["moe"] * 4
    assert set(CFG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    entry = next(c for c in mf.load_manifest()["configs"]
                 if c["name"] == "deepseek-v3-serve")
    assert set(entry["reduced"]) == set(CFG["reduced"])
    for key in ("published", "assumed", "expert_share", "deployment"):
        assert CFG[key], key
    g = W.glm_view(CFG)
    count = lambda leaves: sum(int(np.prod(s)) for s, _ in leaves.values()
                               if len(s) > 1)       # the matrices
    attention = count(W.attention_leaves(CFG))
    expert = count(GW.expert_leaves(g))
    # the issue's arithmetic, matrices alone
    assert round(attention / 1e6, 2) == 187.11
    assert round(expert / 1e6, 2) == 44.04
    dense = attention + count(GW.dense_leaves(g))
    moe = attention + count(GW.moe_leaves(g)) + 16 * expert
    assert round(dense / 1e6, 1) == 583.5 and round(moe / 1e6, 1) == 937.6
    top = count(GW.top_leaves(g))
    assert round(top / 1e6, 1) == 231.7
    held = dense + 4 * moe + top
    assert round(held / 1e9, 3) == 4.566
    whole = 3 * dense + 58 * (moe + 240 * expert) + 2 * 129280 * 7168
    assert round(whole / 1e9, 1) == 671.0
    # a block of 16 tokens: 5 layers x 640 lanes x 2 B, ONE page a layer
    assert deepseek_program.kv_bytes_per_block(CFG, 16) == 102400 \
        == 16 * 6400
    mix = mf.load_json(ROOT / "benchmark/traffic/code_ctx.json")
    slots, s_max = mix["engine"]["capacity"], mix["engine"]["s_max"]
    assert (slots, s_max) == (16, 33792)
    assert mix["engine"]["kv_pool_bytes"] // 102400 == slots * s_max // 16 + 1
    assert s_max == mix["prompt"]["max"] + mix["output"]["max"]
    builder, reference = mf.serve_modules(CFG)
    assert builder is deepseek_program and reference is deepseek_reference
    full = deepseek_program.deepseek_config(CFG)
    assert (full.latent_lanes, full.held_experts, full.n_group,
            full.topk_group, full.factor) == (640, (0, 16), 8, 4, 40)
    assert full.softmax_scale == pytest.approx(z["scale"])
    # drawn so wide that the logits have a spread of LOGIT_STD with the
    # scale in
    assert 0.032 < W.attention_leaves(CFG)["w_uq"][1] < 0.033


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog on this host")
def test_every_number_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CFG["reduced"] or CFG[key] == value, key
    for key in CFG["reduced"]:
        assert CFG["published"][key] == row["config"][key], key


def _layer(cfg=DEBUG, kind="moe", layer=1):
    import jax.numpy as jnp
    return {k: v.astype(jnp.float32) for k, v in W.make_layer(
        W.seed_key(3), cfg, layer, kind, jnp.bfloat16).items()}


def test_every_share_draws_the_same_expert_and_a_small_bias():
    uncut = _layer(dict(DEBUG, n_routed_experts=32,
                        expert_share={"rank": 0, "of": 1}))
    share = _layer(dict(DEBUG, expert_share={"rank": 5, "of": 8}))
    for name in ("we_gate", "we_up", "we_down"):
        np.testing.assert_array_equal(share[name], uncut[name][20:24])
    for name in ("router", "router_bias", "w_uq", "ws_up"):
        np.testing.assert_array_equal(share[name], uncut[name])
    bias = np.asarray(uncut["router_bias"])
    assert 0.003 < np.abs(bias).max() <= 0.005
    assert {round(float(b), 6) for b in bias[:4]} == \
        {-0.00375, -0.00125, 0.00125, 0.00375}


def test_the_references_routing_against_a_slower_spelling():
    """Token by token with numpy: sigmoid scores, a group's score the sum
    of its two largest scores plus bias, the best groups by a stable
    sort, the top-k among their experts, weights the chosen scores over
    their sum, times the scaling factor."""
    import jax
    z, lp = W.sizes(DEBUG), _layer()
    n = jax.random.normal(jax.random.key(0), (48, z["d"]))
    got = np.asarray(deepseek_reference.route(DEBUG, lp["router"],
                                              lp["router_bias"], n))
    router = np.asarray(lp["router"], np.float64)
    bias = np.asarray(lp["router_bias"], np.float64)
    per = z["experts"] // z["n_group"]
    want = np.zeros_like(got, dtype=np.float64)
    outside = 0
    for t, row in enumerate(np.asarray(n, np.float64)):
        scores = 1.0 / (1.0 + np.exp(-(row @ router)))
        choice = scores + bias
        group = np.sort(choice.reshape(-1, per), axis=1)[:, -2:].sum(1)
        kept = np.argsort(-group, kind="stable")[:z["topk_group"]]
        masked = np.where(np.isin(np.arange(z["experts"]) // per, kept),
                          choice, -np.inf)
        chosen = np.argsort(-masked, kind="stable")[:z["top_k"]]
        want[t, chosen] = 2.5 * scores[chosen] / scores[chosen].sum()
        free = np.argsort(-choice, kind="stable")[:z["top_k"]]
        outside += set(free) != set(chosen)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert outside > 5      # the groups do limit the choice


def test_the_controls_are_the_reference_lower_and_the_reference_unscaled():
    import jax.numpy as jnp
    tokens = np.random.default_rng(0).integers(1, DEBUG["vocab_size"], 40)
    positions = np.arange(40)
    ref = np.asarray(deepseek_reference.logits_of(1, DEBUG, tokens, positions))
    low = np.asarray(deepseek_reference.logits_of(1, DEBUG, tokens, positions,
                                                  precision="int8"))
    soft = np.asarray(deepseek_reference.logits_of(
        1, DEBUG, tokens, positions, yarn_scale=False))
    assert ref.shape == low.shape == soft.shape == (40, DEBUG["vocab_size"])
    assert 0 < np.abs(ref - low).max() < 4 * ref.std()
    # one token attends to itself alone whatever the scale
    np.testing.assert_allclose(soft[0], ref[0], atol=1e-6)
    assert np.abs(soft[8:] - ref[8:]).max() > 1e-3
    # the last layer at the compared positions alone is the whole pass's
    some = np.asarray(deepseek_reference.logits_of(1, DEBUG, tokens,
                                                   np.arange(30, 40)))
    np.testing.assert_allclose(some, ref[30:], atol=1e-6)
    gaps = deepseek_reference.served_gaps(1, DEBUG, tokens, 8, control=True)
    assert gaps["served"].shape == gaps["control"].shape == (32,)
    assert (gaps["control"] >= 0).all() and (gaps["served"] >= 0).all()
    assert jnp.isfinite(ref).all()


def test_the_two_counts_against_hand_counts():
    ops, nbytes = mla_decode.needs(ctx_tokens=100000, cfg=CFG)
    assert nbytes == 100000 * 576 * 2 and ops == 100000 * 2 * 128 * 1088
    assert ops / nbytes == pytest.approx(241.8, abs=0.05)
    # at the ridge: the operations bound it, by half a percent
    assert mla_decode.least_seconds(100000, CFG, PEAKS) == \
        pytest.approx(ops / 197e12)
    assert ops / 197e12 == pytest.approx(nbytes / 819e9, rel=0.01)
    assert mla_prefill.pairs(100) == 5050.0
    assert mla_prefill.operations(5050.0, CFG) == 5050 * 2 * 128 * 320
    assert 2 * 128 * 320 == 81920 and 2 * 128 * 1088 == 278528
    # a prompt's first blocks and its last add up to the whole
    assert mla_prefill.pairs(10000) - mla_prefill.pairs(10000 - 256) == \
        256 * (10000 - 256) + 256 * 257 / 2


def test_mla_ctx_tokens_per_step_divides_the_counters(capsys):
    read = layer_metrics.load_reader("mla_ctx_tokens_per_step")
    before = {"mla_ctx_tokens": 1000, "device_steps": 10}
    after = {"mla_ctx_tokens": 501000, "device_steps": 110, "moe_pairs": 4000,
             "moe_expert_visits": 3000, "moe_groups_visited": 2400,
             "prefill_blocks": 50, "decode_row_steps": 900}
    assert read({"before": before, "after": after, "cfg": CFG}) == 5000.0
    assert "held_experts_a_unit 5.00 groups_a_unit 4.00" \
        in capsys.readouterr().out
    assert read({"before": before, "after": before, "cfg": CFG}) is None
    assert read({"before": {"device_steps": 1},
                 "after": {"device_steps": 9}, "cfg": CFG}) is None  # parent
    assert read({}) is None


def _made_up():
    """A span that cuts a prefill at its start, holds a decode chunk of 2
    steps and the first 2 blocks of a prefill its end cuts; 1 expert
    layer of 2, so 3 products a step or block."""
    ev = trace_reduce.Event
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, \
        trace_reduce.MODULES_LINE
    cfg = dict(CFG, num_hidden_layers=2, first_k_dense_replace=1)
    tables = {"jit_decode_chunk_paged": {"mla_latent_decode.3":
                                         "mla_dense_decode",
                                         "fusion.4": "moe_group_route",
                                         "while.9": "mla_dense_decode"},
              "jit_prefill_paged": {"fusion.13": "mla_prefill_attn",
                                    "fusion.14": "moe_shared_ffn"}}
    product = "ragged-dot-none.5 = bf16[8,2048] custom-call("
    events, t = [], 0.0

    def unit(names):
        nonlocal t
        for name, dur in (*names, *[(product, 10.0)] * 3):
            text = name if " = " in name else f"{name} = f32[8] fusion("
            events.append(ev(dev, ops, "%" + text, t, dur))
            t += dur

    prefill = (("fusion.13", 800.0), ("fusion.14", 40.0), ("fusion.99", 500.0))
    unit(prefill)                               # the tail: 1 block
    start = t
    for _ in range(2):                          # a decode chunk: 2 steps
        unit((("mla_latent_decode.3", 60.0), ("fusion.4", 5.0),
              ("fusion.99", 900.0)))
    events.append(ev(dev, ops, "%while.9 = (s32[]) while(", start, t - start))
    events.append(ev(dev, mods, "jit_decode_chunk_paged(7)", start, t - start))
    for _ in range(2):                          # the head: 2 blocks
        unit(prefill)
    log = [[8.0, "prefill", 40, 1, 10240, 0, 0, 0, 0, 1000],
           [8.9, "decode", 8, 3, 90000, 5, 5, 0, 5, 541000],
           [10.2, "decode", 8, 3, 90096, 9, 9, 0, 9, 1081576],
           [10.4, "prefill", 30, 1, 7680, 20, 20, 0, 20, 1081576]]
    return {"trace": trace_reduce.Reduced(events), "peaks": PEAKS, "cfg": cfg,
            "after": {"launches": log, "scopes": tables},
            "trace_span": (10.0, 13.0)}


def test_a_traced_span_is_read_launch_by_launch():
    ctx = _made_up()
    cfg = ctx["cfg"]
    found = dsa_span.segments(ctx)
    assert [(s.kind, s.part, s.units) for s in found] == [
        ("prefill", "tail", 1.0), ("decode", "whole", 2.0),
        ("prefill", "head", 2.0)]
    share = layer_metrics.load_reader("mla_attn_share")(ctx)
    busy = ctx["trace"].busy_s
    assert share == pytest.approx(100 * (3 * 800e-9 + 120e-9) / busy)
    # decode: 2 steps' worth of the one launch near the span (10.2; the
    # one at 8.9 lies over a second ahead): its count a step
    got = layer_metrics.load_reader("mla_decode_roofline")(ctx)
    least = mla_decode.least_seconds(2 * 540576 / 8, cfg, PEAKS)
    assert got == pytest.approx(100 * least / 120e-9)
    # prefill: the tail's last block of the 10240-token prompt launched
    # before the span, and the first 2 blocks of a prompt
    got = layer_metrics.load_reader("mla_prefill_roofline")(ctx)
    pairs = mla_prefill.pairs(10240) - mla_prefill.pairs(9984) \
        + mla_prefill.pairs(512)
    least = mla_prefill.least_seconds(2 * pairs, cfg, PEAKS)
    assert got == pytest.approx(100 * least / (3 * 800e-9))
    # nothing to read: the parent's program (no table), another family,
    # no trace, no peaks
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "mla_attn_share"):
        read = layer_metrics.load_reader(name)
        assert read({**ctx, "after": {"launches": ctx["after"]["launches"]}}) \
            is None
        assert read({**ctx, "cfg": {"hidden_size": 3584}}) is None
        assert read({**ctx, "trace": trace_reduce.Reduced([])}) is None
        assert read({}) is None
    assert layer_metrics.load_reader("mla_decode_roofline")(
        {**ctx, "peaks": None}) is None
    # GLM-5's launches carry other counters behind the device's three:
    # its table names none of this family's scopes, so nothing is read
    glm = {**ctx, "after": {**ctx["after"], "scopes": {
        "jit_decode_chunk_paged": {"fusion.4": "dsa_topk"},
        "jit_prefill_paged": {"fusion.14": "moe_shared_ffn"}}}}
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "mla_attn_share"):
        assert layer_metrics.load_reader(name)(glm) is None


@pytest.mark.skipif(not SLICE.exists(), reason="no recorded slice")
def test_the_readers_on_a_recorded_slice():
    """A decode chunk and blocks of a cold prefill of the code_ctx cell as
    a v5e traced them, read with the engine's table and the launches'
    entries of that run: a share is a share, above 0 and under 100."""
    with open(SLICE) as f:
        rec = json.load(f)
    trace = trace_reduce.Reduced([trace_reduce.Event(*e[:5])
                                  for e in rec["events"]])
    ctx = {"trace": trace, "peaks": PEAKS, "cfg": CFG,
           "after": {"launches": rec["launches"], "scopes": rec["scopes"]},
           "trace_span": rec["trace_span"]}
    found = dsa_span.segments(ctx)
    assert {s.kind for s in found} == {"decode", "prefill"}
    for name in ("mla_decode_roofline", "mla_prefill_roofline",
                 "mla_attn_share"):
        share = layer_metrics.load_reader(name)(ctx)
        assert 0.0 < share < 100.0, name

"""The mimo-v2.5-serve configuration's own pieces: its file against the
catalog's numbers and the issue's arithmetic, the bytes its builder
counts, the reference's routing against a second, slower spelling of it,
the control in the form the limits take, the three counts against hand
counts, and the three readers on made-up counters and on a recorded
slice of a v5e trace."""

import json
import pathlib

import numpy as np
import pytest

from benchmark.kernels import moe_ffn, paged_decode_kv
from benchmark.lib import launch_span, layer_metrics, mimo_program
from benchmark.lib import mimo_reference
from benchmark.lib import manifest as mf
from benchmark.lib import mimo_weights as W
from benchmark.lib import trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = mf.load_json(ROOT / "benchmark/configs/mimo-v2.5-serve.json")
DEBUG = mf.load_json(ROOT / "benchmark/tests/rehearsal/debug-mimo.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_file_holds_the_published_widths_and_states_its_share():
    z = W.sizes(CFG)
    assert (z["d"], z["h"], z["hd"], z["hdv"]) == (4096, 64, 192, 128)
    assert z["kv"] == {"global": 4, "window": 8}
    assert (z["ff"], z["fe"], z["top_k"], z["window"], z["rot"]) == \
        (16384, 2048, 8, 128, 64)
    assert (CFG["rope_theta"], CFG["swa_rope_theta"]) == (1e7, 1e4)
    # the share: 16 of the router's 256 experts, an eighth of the vocabulary
    assert (z["experts"], z["held"], z["first"], z["vocab"]) == \
        (256, 16, 0, 19072)
    assert CFG["published"]["n_routed_experts"] == 256
    assert CFG["published"]["vocab_size"] == 152576 == 8 * 19072
    assert W.kinds(CFG) == [("global", "dense")] + [("window", "moe")] * 4 \
        + [("global", "moe"), ("window", "moe")]
    assert set(CFG["reduced"]) >= {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size", "hybrid_layer_pattern",
                                   "moe_layer_freq"}
    count = lambda leaves: sum(int(np.prod(s)) for s, _ in leaves.values())
    layer = lambda a, f: count(W.norm_leaves(CFG)) \
        + count(W.attention_leaves(CFG, a)) + (
            count(W.dense_leaves(CFG)) if f == "dense" else
            count(W.router_leaves(CFG)) + 16 * count(W.expert_leaves(CFG)))
    held = sum(layer(a, f) for a, f in W.kinds(CFG)) + count(W.top_leaves(CFG))
    assert round(held / 1e9, 2) == 3.43
    # whole: 9 global and 39 window layers, 47 x 256 experts, the whole
    # vocabulary: 309B-A15B as published
    att = {k: count(W.attention_leaves(CFG, k)) for k in ("global", "window")}
    whole = 9 * att["global"] + 39 * att["window"] \
        + count(W.dense_leaves(CFG)) \
        + 47 * (256 * count(W.expert_leaves(CFG)) + 4096 * 256) \
        + 2 * 152576 * 4096
    active = whole - 47 * 248 * count(W.expert_leaves(CFG))
    assert round(whole / 1e9, 1) == 308.8 and round(active / 1e9, 1) == 15.4
    # a block of 16 tokens: 2 global layers x 4 kv heads x (256 + 128)
    assert mimo_program.kv_bytes_per_block(CFG, 16) == 98304
    builder, reference = mf.serve_modules(CFG)
    assert builder is mimo_program and reference is mimo_reference


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog on this host")
def test_every_number_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CFG["reduced"] or CFG[key] == value, key
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert CFG[key] == row["config"][key][:7]


def test_the_references_routing_against_a_slower_spelling():
    """Token by token with numpy: sigmoid scores, the top-k of scores
    plus bias by a sort, weights the chosen scores over their sum."""
    import jax
    import jax.numpy as jnp
    z = W.sizes(DEBUG)
    key = W.seed_key(3)
    lp = {k: v.astype(jnp.float32) for k, v in W.make_layer(
        key, DEBUG, 1, ("window", "moe"), jnp.bfloat16).items()}
    n = jax.random.normal(jax.random.key(0), (40, z["d"]))
    got = np.asarray(mimo_reference.route(DEBUG, lp, n))
    router = np.asarray(lp["router"], np.float64)
    bias = np.asarray(lp["router_bias"], np.float64)
    want = np.zeros_like(got, dtype=np.float64)
    changed = 0
    for t, row in enumerate(np.asarray(n, np.float64)):
        scores = 1.0 / (1.0 + np.exp(-(row @ router)))
        chosen = np.argsort(-(scores + bias), kind="stable")[:z["top_k"]]
        want[t, chosen] = scores[chosen] / scores[chosen].sum()
        changed += set(chosen) != set(np.argsort(-scores)[:z["top_k"]])
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert (got > 0).sum(-1).tolist() == [z["top_k"]] * 40
    assert changed > 0          # the bias chooses, not only in theory


def test_the_control_is_the_reference_in_a_lower_precision():
    import jax.numpy as jnp
    tokens = np.random.default_rng(0).integers(1, DEBUG["vocab_size"], 40)
    positions = np.arange(40)
    ref = np.asarray(mimo_reference.logits_of(1, DEBUG, tokens, positions))
    low = np.asarray(mimo_reference.logits_of(1, DEBUG, tokens, positions,
                                              precision="int8"))
    assert ref.shape == low.shape == (40, DEBUG["vocab_size"])
    assert 0 < np.abs(ref - low).max() < ref.std()
    gaps = mimo_reference.served_gaps(1, DEBUG, tokens, 8, control=True)
    assert gaps["served"].shape == gaps["control"].shape == (32,)
    assert (gaps["control"] >= 0).all() and (gaps["served"] >= 0).all()
    # a sequence past the window and past one block of queries reads the
    # same logits at the positions both have
    short = np.asarray(mimo_reference.logits_of(1, DEBUG, tokens[:20],
                                                positions[:20]))
    np.testing.assert_allclose(short, ref[:20], atol=1e-5)
    assert jnp.isfinite(ref).all()


def test_the_two_counts_against_hand_counts():
    ops, nbytes = moe_ffn.needs(pairs=12, visits=8, hidden=4096, width=2048)
    assert ops == 12 * 6 * 4096 * 2048
    assert nbytes == 8 * 3 * 4096 * 2048 * 2 + 12 * 2 * 4096 * 2
    assert 50.3e6 < 3 * 4096 * 2048 * 2 < 50.4e6          # an expert's bytes
    # a decode step's few pairs: the weights' bytes bound it; a thousand
    # pairs an expert: the operations
    assert moe_ffn.least_seconds(12, 8, 4096, 2048, PEAKS) == \
        pytest.approx(nbytes / 819e9)
    assert moe_ffn.least_seconds(16000, 16, 4096, 2048, PEAKS) == \
        pytest.approx(16000 * 6 * 4096 * 2048 / 197e12)
    ops, nbytes = paged_decode_kv.needs(50000, heads=64, kv_heads=4,
                                        key_width=192, value_width=128)
    assert nbytes == 50000 * 4 * 320 * 2 and ops == 2 * 50000 * 64 * 320
    assert paged_decode_kv.least_seconds(50000, 64, 4, 192, 128, PEAKS) == \
        pytest.approx(nbytes / 819e9)


COUNTERS = {"device_steps": 80, "prefill_blocks": 20, "moe_pairs": 1000,
            "moe_expert_visits": 600, "decode_ctx_tokens": 100000,
            "decode_row_steps": 800}
LATER = {"device_steps": 160, "prefill_blocks": 40, "moe_pairs": 9000,
         "moe_expert_visits": 5400, "decode_ctx_tokens": 4100000,
         "decode_row_steps": 2720}


def test_moe_pairs_per_step_divides_the_counters(capsys):
    read = layer_metrics.load_reader("moe_pairs_per_step")
    assert read({"before": COUNTERS, "after": LATER, "cfg": CFG}) == \
        pytest.approx(100.0)
    # 24 rows a step: an even router brings a layer 24 * 8 * 16 / 256
    # pairs on 16 * (1 - (1 - 8 / 256) ** 24) of the 16 held experts
    out = capsys.readouterr().out
    assert "decode_rows_per_step 24.00" in out
    assert "pairs_per_layer_step 12.00 visits_per_layer_step 8.53" in out
    assert read({"before": COUNTERS, "after": LATER}) == pytest.approx(100.0)
    assert read({"before": COUNTERS, "after": COUNTERS}) is None   # no step
    # a program without the counter: nothing to read, and no error
    assert read({"before": {"device_steps": 1},
                 "after": {"device_steps": 9}}) is None
    assert read({}) is None


def recorded():
    with open(ROOT / "benchmark/tests/data/trace_slice_mimo_v5e.json") as f:
        return trace_reduce.Reduced([trace_reduce.Event(*e[:5])
                                     for e in json.load(f)])


def recorded_launches():
    with open(ROOT / "benchmark/tests/data"
              / "trace_slice_mimo_v5e_launches.json") as f:
        return json.load(f)


def test_the_launches_of_a_traced_span_add_up_each_kind_apart():
    """Entries dispatched inside the span, the counters' differences
    (they run on from launch to launch and may wrap), a kind apart from
    the other; a kernel's events by the program whose launch they lie
    in."""
    log = [[9.0, "decode", 8, 20, 1000, (1 << 32) - 10, 5],
           [10.5, "prefill", 3, 1, 700, 290, 45],       # wrapped: +300, +40
           [11.0, "decode", 8, 22, 1200, 350, 55],
           [11.5, "decode", 8, 24, 1300, 420, 66],
           [13.5, "decode", 8, 30, 9999, 999, 99]]      # past the span
    got = launch_span.span({"after": {"launches": log},
                            "trace_span": (10.0, 13.0)})
    assert got == {
        "prefill": {"launches": 1, "units": 3, "row_units": 3, "tokens": 700,
                    "counters": [300, 40]},
        "decode": {"launches": 2, "units": 16, "row_units": 8 * 46,
                   "tokens": 2500, "counters": [130, 21]}}
    assert launch_span.span({"after": {}, "trace_span": (0, 1)}) is None
    assert launch_span.span({"after": {"launches": log}}) is None
    assert launch_span.span({"after": {"launches": log},
                             "trace_span": (20.0, 23.0)}) is None
    ev = trace_reduce.Event
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, \
        trace_reduce.MODULES_LINE
    trace = trace_reduce.Reduced([
        ev(dev, mods, "jit_decode_chunk_paged(1)", 100.0, 50.0),
        ev(dev, mods, "jit_prefill_paged(2)", 200.0, 50.0),
        ev(dev, mods, "jit_cow_copy(3)", 300.0, 50.0),
        ev(dev, ops, "%k.1 = f32[] custom-call(", 90.0, 5.0),   # cut launch
        ev(dev, ops, "%k.2 = f32[] custom-call(", 110.0, 5.0),
        ev(dev, ops, "%other.3 = f32[] fusion(", 120.0, 5.0),
        ev(dev, ops, "%k.4 = f32[] custom-call(", 210.0, 5.0),
        ev(dev, ops, "%k.5 = f32[] custom-call(", 260.0, 5.0),  # between
        ev(dev, ops, "%k.6 = f32[] custom-call(", 310.0, 5.0)])
    import re
    found = launch_span.events_by_kind(trace, dev, re.compile(r"^%k"))
    assert {k: [e.name[:4] for e in v] for k, v in found.items()} == \
        {"decode": ["%k.2"], "prefill": ["%k.4"]}


@pytest.mark.parametrize("name, pattern", [
    ("moe_ffn_roofline", "%ragged-dot"),
    ("paged_decode_roofline", "%paged_decode_qk192")])
def test_a_roofline_reader_on_a_recorded_slice(name, pattern):
    """One decode chunk and one cold prefill of the long_in cell as a
    v5e traced them, read with what the launches of that run's traced
    span were handed: a share is a share, above 0 and under 100."""
    read = layer_metrics.load_reader(name)
    r, c = recorded(), recorded_launches()
    events = [e for e in r.of(trace_reduce.OPS_LINE)
              if e.name.startswith(pattern)]
    assert events
    ctx = {"trace": r, "peaks": PEAKS, "cfg": CFG,
           "after": {"launches": c["launches"]},
           "trace_span": c["trace_span"]}
    share = read(ctx)
    assert 0.0 < share < 100.0
    # twice the work in the same time is twice the share
    double = [[*e[:4], 2 * e[4], *(2 * v for v in e[5:])]
              for e in c["launches"]]
    assert read({**ctx, "after": {"launches": double}}) == \
        pytest.approx(2 * share, rel=0.02)
    # nothing to read: no kernel in the trace, no launches' entries (the
    # parent's program), none inside the span, another configuration, no
    # peaks, no trace
    assert read({**ctx, "trace": trace_reduce.Reduced([])}) is None
    assert read({**ctx, "after": {"device_steps": 8, "prefill_blocks": 1}}) \
        is None
    assert read({**ctx, "trace_span": (0.0, 1.0)}) is None
    assert read({**ctx, "trace_span": None}) is None
    assert read({**ctx, "cfg": {"hidden_size": 3584}}) is None
    assert read({**ctx, "peaks": None}) is None
    assert read({}) is None

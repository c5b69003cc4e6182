"""The granite-4.0-h-micro-serve configuration's own pieces: its file
against the catalog's shapes, the bytes its builder counts, the count
behind ``ssm_update_roofline`` against a hand count, the three readers on
made-up counters and on a recorded slice of a v5e trace, and the plain
reference against itself in the control's precision."""

import json
import pathlib

import numpy as np
import pytest

from benchmark.kernels import ssm_update
from benchmark.lib import granite_program, granite_reference, layer_metrics
from benchmark.lib import granite_weights as W
from benchmark.lib import manifest as mf
from benchmark.lib import trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[2]
CFG = mf.load_json(ROOT / "benchmark/configs/granite-4.0-h-micro-serve.json")
DEBUG = mf.load_json(ROOT / "benchmark/tests/rehearsal/debug-granite.json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_the_published_shapes_uncut():
    assert CFG["reduced"] == {} and CFG["num_hidden_layers"] == 40
    assert CFG["layer_types"].count("attention") == 4
    assert [i for i, t in enumerate(CFG["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    z = W.sizes(CFG)
    assert (z["di"], z["conv"], z["nh"] * z["hd"]) == (4096, 4352, 4096)
    leaves = lambda kind: sum(int(np.prod(s)) for s, _ in
                              W.layer_leaves(CFG, kind).values())
    top = sum(int(np.prod(s)) for s, _ in W.top_leaves(CFG).values())
    total = 36 * leaves("mamba") + 4 * leaves("attention") + top
    assert round(total / 1e9, 2) == 3.19
    # a block of 16 tokens: 4 attention layers x 8 kv heads x 64, K and V
    assert granite_program.kv_bytes_per_block(CFG, 16) == 131072
    builder, reference = mf.serve_modules(CFG)
    assert builder is granite_program and reference is granite_reference


def test_ssm_update_needs():
    ops, nbytes = ssm_update.needs(1, heads=64, head_dim=64, state=128)
    state = 64 * 64 * 128
    assert nbytes == 4 * (2 * state + 4 * 64 * 64 + 2 * 128)
    assert 2 * 2 ** 21 < nbytes < 1.02 * 2 * 2 ** 21    # 2 MiB in, 2 out
    assert ops == 5 * state
    assert ssm_update.needs(48 * 36, 64, 64, 128)[1] == 48 * 36 * nbytes
    assert ssm_update.least_seconds(1, 64, 64, 128, PEAKS) == \
        pytest.approx(nbytes / 819e9)          # bytes bound it


@pytest.mark.parametrize("name, key", [
    ("ssm_rows_per_step", "ssm_row_steps"),
    ("decode_rows_per_step", "decode_row_steps")])
def test_a_rows_per_step_reader_divides_the_counters(name, key):
    read = layer_metrics.load_reader(name)
    before = {"device_steps": 80, key: 800}
    after = {"device_steps": 160, key: 4000}
    assert read({"before": before, "after": after}) == pytest.approx(40.0)
    assert read({"before": before, "after": before}) is None    # no step
    # a program without the counter: nothing to read, and no error
    assert read({"before": {"device_steps": 1},
                 "after": {"device_steps": 9}}) is None
    assert read({}) is None


def recorded():
    with open(ROOT / "benchmark/tests/data/trace_slice_granite_v5e.json") as f:
        return trace_reduce.Reduced([trace_reduce.Event(*e)
                                     for e in json.load(f)])


def test_ssm_update_roofline_on_a_recorded_slice():
    """One decode chunk of the chat_short cell as a v5e traced it: 8
    steps x 36 layers of the kernel under its own name. Read with the
    rows the chunk carried, the share is a share: above 0, under 100."""
    read = layer_metrics.load_reader("ssm_update_roofline")
    r = recorded()
    events = [e for e in r.of(trace_reduce.OPS_LINE)
              if e.name.startswith("%ssm_decode_update")]
    assert len(events) == 8 * 36
    with open(ROOT / "benchmark/tests/data/trace_slice_granite_v5e_rows.txt") as f:
        rows = float(f.read())
    ctx = {"trace": r, "peaks": PEAKS, "cfg": CFG,
           "before": {"device_steps": 0, "ssm_row_steps": 0},
           "after": {"device_steps": 8, "ssm_row_steps": 8 * rows}}
    share = read(ctx)
    assert 0.0 < share < 100.0
    assert share == pytest.approx(
        100 * ssm_update.least_seconds(rows * len(events), 64, 64, 128, PEAKS)
        / (sum(e.dur_ns for e in events) / 1e9))
    # nothing to read: no kernel in the trace, no counter, no trace
    assert read({**ctx, "trace": trace_reduce.Reduced([])}) is None
    assert read({**ctx, "after": {"device_steps": 8}}) is None
    assert read({**ctx, "cfg": {"hidden_size": 3584}}) is None
    assert read({}) is None


def test_the_control_is_the_reference_in_a_lower_precision():
    """Same sequence through the float32 reference and the int8 control:
    logits that agree in shape and differ in value, the control's first
    choice nowhere above the reference's own best."""
    tokens = np.random.default_rng(0).integers(1, DEBUG["vocab_size"], 40)
    positions = np.arange(40)
    ref = np.asarray(granite_reference.logits_of(1, DEBUG, tokens, positions))
    low = np.asarray(granite_reference.logits_of(1, DEBUG, tokens, positions,
                                                 precision="int8"))
    assert ref.shape == low.shape == (40, DEBUG["vocab_size"])
    assert 0 < np.abs(ref - low).max() < ref.std()
    gaps = granite_reference.served_gaps(1, DEBUG, tokens, 8, control=True)
    assert gaps["served"].shape == gaps["control"].shape == (32,)
    assert (gaps["control"] >= 0).all() and (gaps["served"] >= 0).all()

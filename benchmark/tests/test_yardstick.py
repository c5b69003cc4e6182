"""Generator determinism, metric arithmetic, the trace reduction on a
recorded slice, the ops/bytes functions against hand counts, and the
manifest's files and names."""

import json
import pathlib
import re

import numpy as np
import pytest

from benchmark.kernels import flash_fwd, flash_train, paged_decode, train_step
from benchmark.lib import manifest as mf
from benchmark.lib import layer_metrics, stats, trace_reduce, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = mf.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic -----------------------------------------------------------------
def mix(name):
    return mf.load_json(mf.traffic_path(name))


@pytest.mark.parametrize("name", ["chat", "doc_qa"])
def test_schedule_is_a_function_of_the_seed(name):
    a = traffic.schedule(mix(name), 2 ** 31 + 5, 45.0, 152064)
    b = traffic.schedule(mix(name), 2 ** 31 + 5, 45.0, 152064)
    assert [s.due_s for s in a] == [s.due_s for s in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for s, t in zip(a, b) for x, y in zip(s.turns, t.turns))


@pytest.mark.parametrize("name", ["chat", "doc_qa"])
def test_every_seed_offers_the_same_work(name):
    a = traffic.schedule(mix(name), 1, 45.0, 152064)
    b = traffic.schedule(mix(name), 99, 45.0, 152064)

    def sizes(sessions):
        # the same documents, questions and answer lengths, paired anew
        return (sorted(s.turns[0].prompt.size - s.turns[0].fresh_len
                       for s in sessions),
                sorted(t.fresh_len for s in sessions for t in s.turns),
                sorted(t.max_new for s in sessions for t in s.turns))
    assert sizes(a) == sizes(b)
    assert len(a) == len(b)
    # the mix fixes the order (``order_seed``): same times, same lengths
    # in the same places, other tokens
    assert [s.due_s for s in a] == [s.due_s for s in b]
    assert [t.max_new for s in a for t in s.turns] == \
        [t.max_new for s in b for t in s.turns]
    assert not np.array_equal(a[0].turns[0].prompt, b[0].turns[0].prompt)
    # another ``order_seed`` is the same set in another order
    c = traffic.schedule({**mix(name), "order_seed": 8}, 1, 45.0, 152064)
    assert sizes(c) == sizes(a)
    assert [s.due_s for s in c] != [s.due_s for s in a]


@pytest.mark.parametrize("name", ["chat", "doc_qa"])
def test_lengths_stay_inside_the_mix(name):
    m = mix(name)
    longest_prompt, longest_new = traffic.longest_request(m)
    for s in traffic.schedule(m, 7, 45.0, 152064):
        for t in s.turns:
            assert m["prompt"]["min"] <= t.fresh_len <= m["prompt"]["max"]
            assert t.prompt.size <= longest_prompt
            assert m["output"]["min"] <= t.max_new <= longest_new
            assert t.prompt.min() >= 1 and t.prompt.max() < 152064
    assert longest_prompt + longest_new <= m["engine"]["s_max"]


def test_bursts_and_heavy_tails_for_the_mixes_to_come():
    """No mix uses them yet (PERF.md, Open question 3); a later PR may add
    a mix but no generator code."""
    burst = {**mix("chat"), "arrivals": {
        "process": "bursty", "rate_per_s": 1.3, "burst_factor": 4.0,
        "on_dwell_s": 2.0, "off_dwell_s": 6.0}}
    a = traffic.schedule(burst, 1, 45.0, 152064)
    b = traffic.schedule(burst, 2, 45.0, 152064)
    assert a and [s.due_s for s in a] == [s.due_s for s in b]
    assert all(0.0 < s.due_s < 45.0 for s in a)
    tail = traffic.lengths({"dist": "pareto", "alpha": 1.2, "min": 16,
                            "max": 2048}, 200, np.random.default_rng(0))
    assert tail.min() == 16 and 16 < np.median(tail) < 64 < tail.max() <= 2048


def test_no_two_sessions_open_alike():
    sessions = traffic.schedule(mix("chat"), 3, 45.0, 152064)
    leads = [s.turns[0].prompt[0] for s in sessions]
    assert len(set(leads)) == len(leads)
    warm = traffic.warmup_prompt(np.random.default_rng(0), 152064, 24, 5)
    assert warm[0] not in leads


def test_sessions_share_their_document():
    s = traffic.schedule(mix("doc_qa"), 3, 45.0, 152064)[0]
    doc = s.turns[0].prompt.size - s.turns[0].fresh_len
    assert doc >= mix("doc_qa")["document"]["min"]
    assert all(np.array_equal(t.prompt[:doc], s.turns[0].prompt[:doc])
               for t in s.turns)
    assert not np.array_equal(s.turns[0].prompt[doc:doc + 8],
                              s.turns[1].prompt[doc:doc + 8])


# -- metric arithmetic -------------------------------------------------------
def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_a_failed_request_counts_as_the_window():
    assert stats.with_failures([0.1, 0.2], 2, 45.0) == [0.1, 0.2, 45.0, 45.0]
    lat = stats.with_failures([0.1] * 18, 2, 45.0)
    assert stats.percentile(lat, 95) == 45.0


def test_spread_is_the_quartile_distance_over_the_median():
    v = [10, 11, 12, 13, 14, 15]
    assert stats.iqr_share(v) == pytest.approx((14.25 - 10.75) / 12.5)


def test_a_set_is_read_for_tightness_without_its_farthest_run():
    # one far-off run does no harm, two do
    one = stats.without_farthest([100, 101, 99, 100.5, 99.5, 140])
    assert sorted(one) == [99, 99.5, 100, 100.5, 101]
    assert stats.range_share(one) == pytest.approx(2 / 100)
    two = stats.without_farthest([100, 101, 99, 100, 130, 140])
    assert stats.range_share(two) == pytest.approx(31 / 100)
    assert stats.iqr_share(two) > 10 * stats.iqr_share(one)
    assert stats.without_farthest([7.0, 7.0]) == [7.0, 7.0]


def test_samples_beyond_a_nearest_rank_percentile():
    assert stats.beyond(200, 95) == 10 and stats.beyond(199, 95) == 9
    assert stats.beyond(58, 95) == 2 and stats.beyond(0, 95) == 0


# -- trace reduction ---------------------------------------------------------
def test_union_and_gaps():
    iv = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert trace_reduce.union_ns(iv) == 22
    assert trace_reduce.gaps_ns(iv, 0, 40) == [(12, 20), (30, 40)]


def recorded():
    with open(ROOT / "benchmark/tests/data/trace_slice_v5e.json") as f:
        return trace_reduce.Reduced([trace_reduce.Event(*e) for e in json.load(f)])


def test_recorded_slice_reduces():
    r = recorded()
    assert r.devices() == ["/device:TPU:0"]
    assert 0 < r.busy_s <= r.window_s
    # hand count: the busy time is the union, never the sum, of the ops
    ops = r.of(trace_reduce.OPS_LINE)
    assert r.busy_s <= sum(e.dur_ns for e in ops) / 1e9
    assert 0.0 <= r.idle_share() < 1.0
    mods = r.seconds_by(trace_reduce.MODULES_LINE)
    assert any(k.startswith("jit_prefill_paged") for k in mods)
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(not k.startswith("while") for k, _ in b["device_ops"])


def test_labels_are_short_and_stable():
    assert trace_reduce.short_name("jit_decode_chunk_paged(123)") == \
        "jit_decode_chunk_paged"
    assert trace_reduce.label(
        "%copy.80 = bf16[14,4141,4,16,128]{4,2,3,1,0:T(4,128)(2,1)} "
        "copy(bf16[14,4141,4,16,128]{4,2,3,1,0} %x)") == \
        "copy bf16[14,4141,4,16,128]"


def test_exposed_collectives():
    ev = [trace_reduce.Event("/device:TPU:0", trace_reduce.OPS_LINE, n, a, d)
          for n, a, d in [("%all-reduce.1 = x all-reduce(", 0, 10),
                          ("%fusion.1 = x fusion(", 5, 10)]]
    r = trace_reduce.Reduced(ev)
    assert r.exposed_seconds(lambda n: "all-reduce" in n) == pytest.approx(5e-9)


# -- ops and bytes, against hand counts --------------------------------------
def test_paged_decode_needs():
    ops, nbytes = paged_decode.needs([100, 28], heads=28, kv_heads=4,
                                     head_dim=128)
    assert nbytes == 128 * 4 * 128 * 2 * 2 + 2 * 2 * 28 * 128 * 2
    assert ops == 4 * 128 * 28 * 128
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert paged_decode.least_seconds([100, 28], 28, 4, 128, peaks) == \
        pytest.approx(nbytes / 819e9)          # bytes bound it


def test_flash_needs():
    ops, nbytes = flash_fwd.needs(1, 2048, 32, 8, 128)
    assert ops == 4 * 32 * 128 * 2048 * 2048 / 2
    assert nbytes == 2048 * 128 * (64 + 16) * 2
    t_ops, t_bytes = flash_train.needs(1, 2048, 32, 8, 128)
    assert t_ops == 3 * ops and t_bytes > nbytes


def test_train_step_flops_per_token():
    cfg = mf.load_json(ROOT / "benchmark/configs/mistral-7b-train.json")
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert train_step.matmul_params(cfg) == 4 * per_layer + 4096 * 32768
    assert train_step.flops_per_token(cfg, 4096) == \
        6 * train_step.matmul_params(cfg) + 6 * 4096 * 32 * 128 * 4


# -- the manifest ------------------------------------------------------------
def test_manifest_names_units_and_files():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        body = mf.load_json(ROOT / c["file"])
        assert set(c["reduced"]) == set(body["reduced"])
        for key in ("source", "reduced", "assumed", "deployment"):
            assert key in body
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert mf.traffic_path(w["traffic"]).is_file()
        assert w["config"] in {c["name"] for c in m["configs"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1
    for metric in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}
    for e in m["end_to_end"]:
        assert 0 < e["bound"] <= 0.1
    # the most that fits a full check of 24 cells (the contract)
    assert 1 <= m["run_seconds"] <= 51


SERVE_CELLS = [w for w in MANIFEST["workloads"]
               if mix(w["traffic"])["runner"] == "serve"]


@pytest.mark.parametrize("cell", SERVE_CELLS, ids=lambda w: w["name"])
def test_a_serve_cell_offers_ten_samples_past_its_p95(cell):
    """The rule of PERF.md, section 2: a tail is reported at a percentile
    with ten samples beyond it, so the schedule of ``run_seconds`` holds
    at least 200 requests (sessions x turns; a session's later turns are
    due only if the system answers, which the chip runs show)."""
    m = mix(cell["traffic"])
    cfg = mf.cell_files(MANIFEST, cell)[0]
    sessions = traffic.schedule(m, 1, float(MANIFEST["run_seconds"]),
                                cfg["vocab_size"])
    offered = sum(len(s.turns) for s in sessions)
    assert stats.beyond(offered, 95) >= 10, offered


@pytest.mark.parametrize("cell", SERVE_CELLS, ids=lambda w: w["name"])
def test_a_why_that_names_a_rate_names_the_mix_files_own(cell):
    rates = re.findall(r"(\d+(?:\.\d+)?) *(?:req|requests|sessions)/s",
                       cell["why"])
    own = mix(cell["traffic"])["arrivals"]["rate_per_s"]
    assert rates and all(float(r) == own for r in rates), (rates, own)


def test_a_configuration_names_its_builder_and_reference():
    """Absent keys mean the Llama builder and decoder reference; written
    out they find the same modules, each with what the runner asks for."""
    default = mf.serve_modules({})
    named = mf.serve_modules({"program": {"build": "program",
                                          "reference": "reference"}})
    assert default == named
    builder, ref = default
    assert callable(builder.build_model) and callable(ref.served_gaps)
    cfg = mf.load_json(ROOT / "benchmark/configs/qwen2-7b-serve.json")
    assert builder.kv_bytes_per_block(cfg, 16) == 2 * 14 * 4 * 16 * 128 * 2
    with pytest.raises(ModuleNotFoundError):
        mf.serve_modules({"program": {"build": "no_such_builder"}})


def test_every_per_layer_metric_has_a_reader_and_a_target():
    e2e = {e["name"] for e in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for metric in MANIFEST["per_layer"]:
        assert metric["moves"] in e2e
        assert (ROOT / "benchmark/layer_metrics"
                / f"{metric['name']}.py").is_file()
        assert set(metric.get("workloads", [])) <= cells
        assert callable(layer_metrics.load_reader(metric["name"]))


def test_prefill_blocks_share_reads_the_counters_difference():
    read = layer_metrics.load_reader("prefill_blocks_share")
    before = {"prefill_blocks": 10, "prefill_window_blocks": 100}
    after = {"prefill_blocks": 13, "prefill_window_blocks": 120}
    assert read({"before": before, "after": after}) == pytest.approx(15.0)
    assert read({"before": before, "after": before}) is None   # no cold prefill
    assert read({"before": {}, "after": {"admitted": 3}}) is None
    assert read({}) is None


@pytest.mark.parametrize("name, whole, answered", [
    ("ttft_p95_ms", 51000.0, 117.0), ("ttft_p50_ms", 109.0, 108.0)])
def test_a_ttft_reader_takes_the_runners_own_list(name, whole, answered):
    # 18 requests answered in 100-117 ms and 2 with no first token, which
    # count as the window's length: the tail shows them, the median not
    read = layer_metrics.load_reader(name)
    ttft = stats.with_failures([0.1 + i / 1e3 for i in range(18)], 2, 51.0)
    assert read({"latencies": {"ttft": ttft}}) == pytest.approx(whole)
    assert read({"latencies": {"ttft": ttft[:18]}}) == pytest.approx(answered)
    assert read({}) is None


def test_unknown_device_kind_is_an_error():
    from benchmark.lib import device
    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99")

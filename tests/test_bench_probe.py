"""bench.py and chip_smoke.py at their edges: a measurement path that
finds no accelerator fails (no probe child, no wall, no infra-skip line,
no CPU fallback), the peak table refuses a chip it does not know, and the
compile cache lands where it was told. The slow cases run whole bench
presets on the CPU with ``BENCH_ALLOW_CPU=1`` — counts, not device
metrics."""

import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench  # noqa: E402


@pytest.mark.slow
def test_prefix_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=prefix (ISSUE 2 satellite):
    one JSON line, cached TTFT strictly below uncached (vs_baseline is
    their ratio), and the engine actually served prefix hits. r8: the
    run also dumps the engine's metrics-registry snapshot and links it
    from extra.metrics_snapshot."""
    env = dict(os.environ, BENCH_PRESET="prefix", BENCH_ALLOW_CPU="1",
                              BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "prefix_cached_ttft_ms"
    assert out["value"] > 0
    assert out["vs_baseline"] > 1.0    # cached strictly beats uncached
    assert out["extra"]["prefix_hit_tokens"] > 0
    assert out["extra"]["uncached_ttft_ms"] > out["value"]
    snap_path = out["extra"]["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_prefix.json")
    snap = json.load(open(snap_path))
    assert snap["counters"]["engine_prefix_hit_tokens_total"] > 0
    assert snap["histograms"]["engine_ttft_seconds"]["count"] > 0


@pytest.mark.slow
def test_fleet_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=fleet (ISSUE 4 satellite):
    one JSON line, prefix-affinity routing strictly beats round-robin
    on the shared-system-prompt workload (vs_baseline = rr/affinity
    cached TTFT > 1, and more prefix tokens served from cache), and the
    aggregated per-worker + merged registry snapshot is dumped."""
    env = dict(os.environ, BENCH_PRESET="fleet", BENCH_ALLOW_CPU="1",
                              BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "fleet_affinity_ttft_ms"
    assert out["value"] > 0
    assert out["vs_baseline"] > 1.0    # affinity beats round-robin
    assert out["extra"]["affinity_prefix_hit_tokens"] > \
        out["extra"]["rr_prefix_hit_tokens"]
    assert out["extra"]["affinity_hits"] > 0
    snap_path = out["extra"]["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_fleet.json")
    snap = json.load(open(snap_path))
    assert set(snap["workers"]) == {"w0", "w1", "router"}
    merged = snap["fleet"]
    assert merged["counters"]["engine_prefix_hit_tokens_total"] > 0
    assert merged["counters"]["fleet_submitted_total"] == \
        snap["workers"]["router"]["counters"]["fleet_submitted_total"]
    assert merged["histograms"]["engine_ttft_seconds"]["count"] == sum(
        snap["workers"][w]["histograms"]["engine_ttft_seconds"]["count"]
        for w in ("w0", "w1"))


@pytest.mark.slow
def test_slo_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=slo (ISSUE 5 satellite): one
    JSON line, the SLO engine + shipper cost under 5% of step wall (the
    acceptance budget), the shipper actually delivered telemetry to the
    JSONL sink, and the aggregated snapshot carries the shipper's
    self-observation counters."""
    env = dict(os.environ, BENCH_PRESET="slo", BENCH_ALLOW_CPU="1",
                              BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "slo_shipper_overhead_pct"
    assert out["value"] < 5.0          # telemetry tax under the 5% budget
    assert out["vs_baseline"] > 0.95
    ship = out["extra"]["shipper"]
    assert ship["shipped"] > 0
    assert ship["sink_errors"] == 0
    assert out["extra"]["slo_states"] == {"ttft_p99": "ok",
                                          "error_rate": "ok"}
    with open(out["extra"]["telemetry_jsonl"]) as fh:
        payloads = [json.loads(ln) for ln in fh if ln.strip()]
    assert payloads and all(p["kind"] == "fleet_telemetry"
                            for p in payloads)
    snap_path = out["extra"]["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_slo.json")
    snap = json.load(open(snap_path))
    assert "shipper" in snap["workers"]
    assert snap["workers"]["shipper"]["counters"][
        "shipper_shipped_total"] > 0


@pytest.mark.slow
def test_overload_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=overload (ISSUE 6 satellite):
    one JSON line; the QoS accounting (admitted/throttled/shed/served
    on the virtual clock) replays bit-identically across the two QoS-on
    sims (extra.qos.deterministic); every shed request is accounted
    (tally shed == qos_shed_total sum == shed_rate * submitted); and
    Jain's fairness index is recorded for both configs with the
    aggregated snapshot dumped."""
    env = dict(os.environ, BENCH_PRESET="overload",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "overload_p99_ttft_ms"
    assert out["value"] > 0
    extra = out["extra"]
    # the virtual-clock policy replay must be bit-deterministic
    assert extra["qos"]["deterministic"] is True
    for key in ("jain_fairness_on", "jain_fairness_off"):
        assert 0.0 < extra[key] <= 1.0
    assert out["vs_baseline"] == pytest.approx(
        extra["jain_fairness_on"] / extra["jain_fairness_off"],
        rel=1e-3)
    # shed accounting: tally == per-tenant counters == shed_rate
    shed_tally = sum(t["shed"] for t in extra["tally_on"].values())
    shed_counters = sum(int(t["shed"]) for t in
                        extra["qos"]["per_tenant"].values())
    assert shed_tally == shed_counters == extra["qos"]["shed_total"]
    assert extra["shed_rate"] == pytest.approx(
        extra["qos"]["shed_total"] / extra["submitted"], abs=1e-3)
    # the flood engaged all three policies under the fixed seed
    assert extra["qos"]["shed_total"] > 0
    assert sum(int(t["throttled"]) for t in
               extra["qos"]["per_tenant"].values()) > 0
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_overload.json")
    snap = json.load(open(snap_path))
    assert "tenant=t_heavy" in snap["workers"]
    assert "tenant=t_light" in snap["workers"]
    assert snap["workers"]["tenant=t_light"]["counters"][
        "qos_shed_total"] == 0
    assert snap["fleet"]["histograms"]["engine_ttft_seconds"][
        "count"] > 0


@pytest.mark.slow
def test_mixed_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=mixed (ISSUE 7 satellite):
    one JSON line; the chunked and admission runs of the same seeded
    flood produce bit-identical greedy outputs; chunked p99 TTFT is no
    worse than admission p99 TTFT (the perf claim, on the same engine
    config); and the chunk windows stayed inside the documented bucket
    set (no third program shape)."""
    env = dict(os.environ, BENCH_PRESET="mixed",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "mixed_p99_ttft_ms"
    assert out["value"] > 0
    extra = out["extra"]
    # the correctness oracle: same flood, same greedy outputs
    assert extra["outputs_identical"] is True
    # the perf claim: chunking flattens (or at worst matches) the tail
    assert (extra["chunked_p99_ttft_ms"]
            <= extra["admission_p99_ttft_ms"])
    assert out["vs_baseline"] >= 1.0
    # shape discipline: every chunk window is a documented power-of-two
    # bucket (the default page-sized chunk rides exactly {16})
    assert extra["chunk_prog_windows"] == [16]
    assert extra["prefill_chunks"] > 0
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_mixed.json")
    snap = json.load(open(snap_path))
    assert snap["counters"]["engine_prefill_chunks_total"] == \
        extra["prefill_chunks"]
    assert snap["histograms"]["engine_step_budget_used"]["count"] > 0
    # ISSUE 13: the phase-breakdown dump rides beside the metrics one
    prof = json.load(open(extra["profile_snapshot"]))
    assert prof["chunked"]["steps"] > 0
    assert "prefill_chunk" in prof["chunked"]["phases"]
    assert prof["compiles"]["chunked"]["unexpected"] == 0


@pytest.mark.slow
def test_spec_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=spec (ISSUE 8 satellite):
    one JSON line; spec ON emits bit-identical outputs to plain greedy
    on the same seeded prompt mix (the speculation oracle — every
    accepted token is the verify program's argmax); the draft-friendly
    repetitive mix earns at least 1.2 tokens per verify step; and the
    accept accounting in the snapshot is self-consistent with the
    BENCH row."""
    env = dict(os.environ, BENCH_PRESET="spec",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "spec_tokens_per_step"
    extra = out["extra"]
    # the correctness oracle: speculation changes WHEN tokens are
    # computed, never WHICH tokens come out
    assert extra["outputs_identical"] is True
    # the perf claim: drafts pay on the repetitive mix
    assert out["value"] >= 1.2
    assert 1.0 <= extra["tokens_per_step_mix"] <= out["value"] + 1e-9
    assert 0.0 < extra["accept_rate_mix"] <= 1.0
    assert extra["accepted"] <= extra["proposed"]
    # deterministic accounting: the snapshot's counters back the row
    snap = json.load(open(extra["metrics_snapshot"]))
    assert snap["counters"]["engine_spec_proposed_total"] == \
        extra["proposed"]
    assert snap["counters"]["engine_spec_accepted_total"] == \
        extra["accepted"]
    assert snap["histograms"]["engine_spec_accept_len"]["count"] > 0


@pytest.mark.slow
def test_tp_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=tp (ISSUE 10 satellite): one
    JSON line; sharded (tp=2 and tp=4) outputs bit-identical to the
    unsharded engine on the same seeded arrivals; the tp=2 repeat is
    bit-for-bit (same outputs AND same launch count); and the batched
    verify + single-launch mixed step genuinely collapse per-step
    device calls (sharded launches/step ~1, unsharded strictly
    higher)."""
    env = dict(os.environ, BENCH_PRESET="tp",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "tp_device_calls_per_step"
    extra = out["extra"]
    # the correctness oracle: sharding is device wiring, never a
    # quality trade
    assert extra["outputs_identical_tp2"] is True
    assert extra["outputs_identical_tp4"] is True
    assert extra["repeat_bit_identical"] is True
    # the perf claim: O(rows) per-row verify launches collapse into
    # O(1) mixed launches per engine step
    assert out["vs_baseline"] > 1.0
    assert extra["tp2_device_calls"] < extra["unsharded_device_calls"]
    assert out["value"] < extra["unsharded_calls_per_step"]
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_tp.json")
    snap = json.load(open(snap_path))
    assert snap["counters"]["engine_device_calls_total"] > 0
    assert snap["gauges"]["engine_tp_degree"] == 2


@pytest.mark.slow
def test_cp_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=cp (ISSUE 16 satellite): one
    JSON line; the 1-D tp=4 and 2-D (seq=2, tp=4) runs both bit-match
    the unsharded oracle on the same seeded long-prompt flood; the 2-D
    repeat is bit-for-bit with an equal launch count; and the wider
    context-parallel prefill chunk genuinely flattens the long-prompt
    TTFT tail (p99 in engine steps strictly better than 1-D tp at the
    kv-head cap, with strictly fewer device launches)."""
    env = dict(os.environ, BENCH_PRESET="cp",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "cp_p99_ttft_steps"
    extra = out["extra"]
    # the correctness oracle: the 2-D mesh is device wiring, never a
    # quality trade — and the same seed replays bit-for-bit
    assert extra["outputs_identical_tp4"] is True
    assert extra["outputs_identical_2d"] is True
    assert extra["repeat_bit_identical"] is True
    # the perf claim: spreading chunk windows over the seq axis cuts
    # the prefill launches a long prompt needs, so the TTFT tail drops
    assert out["vs_baseline"] > 1.0
    assert out["value"] < extra["tp4_p99_ttft_steps"]
    assert extra["seq2_tp4_device_calls"] < extra["tp4_device_calls"]
    assert extra["mesh_shape"] == {"seq": 2, "tp": 4}
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_cp.json")
    snap = json.load(open(snap_path))
    assert snap["gauges"]["engine_tp_degree"] == 4
    assert snap["gauges"]["engine_seq_degree"] == 2


@pytest.mark.slow
def test_chaos_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=chaos (ISSUE 9 satellite):
    one JSON line; the same-seed chaos run replays bit-for-bit; every
    output completed under faults bit-matches the fault-free run
    (failover is recompute-resume); and the fleet healed back to full
    capacity by the end of the window."""
    env = dict(os.environ, BENCH_PRESET="chaos",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "chaos_goodput_ratio"
    extra = out["extra"]
    # same seed, same faults, same outputs — bit-for-bit
    assert extra["deterministic"] is True
    # the healing oracle: whatever completed under chaos matches the
    # fault-free run token-for-token
    assert extra["outputs_bit_parity"] is True
    assert extra["compared_outputs"] > 0
    # the schedule genuinely injected faults and the fleet healed
    assert sum(extra["faults_fired"].values()) > 0
    assert extra["restarts"] > 0
    assert extra["healthy_workers_end"] == 3
    assert 0.0 < out["value"] <= 1.0
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_chaos.json")
    snap = json.load(open(snap_path))
    assert snap["fleet"]["counters"]["engine_retired_total"] > 0
    # ISSUE 13: the measured chaos run is profiled and bundle-dumping
    # (the plain repeat proves the observers didn't perturb it —
    # deterministic above); every failover left a postmortem bundle
    assert extra["postmortem_bundles"] > 0
    prof = json.load(open(extra["profile_snapshot"]))
    assert prof["statusz"]["router_profile"]["steps"] > 0
    assert len(prof["postmortems"]) == extra["postmortem_bundles"]
    assert all(n.startswith("postmortem_") for n in prof["postmortems"])


@pytest.mark.slow
def test_disagg_preset_cpu_smoke(tmp_path):
    """End-to-end CPU run of BENCH_PRESET=disagg (ISSUE 14 satellite):
    one JSON line; the role-split and unified runs of the same seeded
    two-tenant mix produce bit-identical greedy outputs; the split
    fleet's prompt-tenant p99 TTFT beats unified (the perf claim —
    decode residency moved off the prefill worker); the same-seed
    split repeat replays bit-for-bit; and the KV pages genuinely moved
    over the transplant path (migration counters in the row AND the
    merged registry snapshot, zero in the unified run)."""
    env = dict(os.environ, BENCH_PRESET="disagg",
               BENCH_ALLOW_CPU="1",
               BENCH_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, bench.__file__], env=env,
                       capture_output=True, text=True, timeout=170)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert len(lines) == 1                         # one-JSON-line contract
    out = json.loads(lines[0])
    assert out["metric"] == "disagg_p99_ttft_ms"
    assert out["value"] > 0
    extra = out["extra"]
    # the correctness oracle: disaggregation moves WHERE tokens are
    # computed, never WHICH tokens come out
    assert extra["outputs_identical"] is True
    # the same-seed split repeat replays bit-for-bit (tokens AND
    # migration counters — no wall times in the signature)
    assert extra["deterministic"] is True
    # the perf claim: a dedicated prefill worker flattens the
    # prompt-heavy tenant's TTFT tail
    assert out["vs_baseline"] > 1.0
    assert extra["split_p99_ttft_ms"] < extra["unified_p99_ttft_ms"]
    # pages really rode the transplant path — and only in split mode
    assert extra["migrations"] > 0
    assert extra["migrated_pages"] >= extra["migrations"]
    assert extra["unified_migrations"] == 0
    snap_path = extra["metrics_snapshot"]
    assert snap_path == str(tmp_path / "bench_metrics_disagg.json")
    snap = json.load(open(snap_path))
    assert set(snap["workers"]) == {"w0", "w1", "router"}
    merged = snap["fleet"]["counters"]
    assert merged["fleet_migrations_total"] == extra["migrations"]
    assert merged["fleet_kv_migrated_pages_total"] == \
        extra["migrated_pages"]


def test_staticcheck_cli_clean_in_process(capsys):
    """graftcheck (ISSUE 11 + 12) gates the tree this bench drives —
    bench.py itself is in the scan set. In-process like the probe
    tests above (no subprocess spawn): the CLI must exit 0 at HEAD,
    and the nine-checker run (per-file passes + the shared call
    graph) must stay inside its CI latency budget — the parse/graph
    caches are what keep interprocedural analysis from turning the
    gate into the slowest job in the pipeline."""
    from paddle_tpu.staticcheck.__main__ import main
    t0 = time.perf_counter()
    assert main([]) == 0
    elapsed = time.perf_counter() - t0
    assert "0 findings" in capsys.readouterr().out
    assert elapsed < 3.0, (
        f"nine-checker staticcheck run took {elapsed:.2f}s — the "
        f"parse-once/graph-once caches have regressed")
    # the ISSUE 12 CLI surface: CI annotation format (clean tree ->
    # zero annotation lines) and SC range syntax both run end to end
    assert main(["--format=github"]) == 0
    assert capsys.readouterr().out == ""
    assert main(["--checkers", "SC06-SC09"]) == 0
    assert "0 findings" in capsys.readouterr().out


def test_observability_dump_cli_in_process(tmp_path, capsys):
    """ISSUE 13 satellite: the ``python -m paddle_tpu.observability.dump``
    CLI, driven in-process like the staticcheck gate above. One bundle
    lands in the target dir from the process-default flight recorder +
    registry; usage errors exit 2, help exits 0."""
    from paddle_tpu.observability.dump import USAGE, main
    from paddle_tpu.observability.flight import get_flight_recorder
    get_flight_recorder().record("cli_smoke", origin="test")
    assert main([str(tmp_path), "cli-smoke"]) == 0
    printed = capsys.readouterr().out.strip()
    assert printed.endswith(".json") and os.path.exists(printed)
    bundle = json.load(open(printed))
    assert bundle["reason"] == "cli-smoke"
    assert any(e["kind"] == "cli_smoke"
               for e in bundle["flight"]["events"])
    assert "counters" in bundle["metrics"]
    # usage surface
    assert main([]) == 2
    assert USAGE in capsys.readouterr().err
    assert main(["-h"]) == 0
    assert USAGE in capsys.readouterr().out


@pytest.mark.slow
def test_step_profiler_overhead_under_5pct():
    """ISSUE 13 acceptance: the per-step phase timer must cost < 5%
    wall overhead on the CPU debug engine. Interleaved min-of-5 — the
    minimum is the honest estimator under CI noise, and interleaving
    keeps thermal/cache drift from biasing one arm."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import DecodeEngine

    paddle.seed(0)
    from paddle_tpu.models.llama import LlamaForCausalLM
    m = LlamaForCausalLM("debug")
    m.eval()
    rng = np.random.RandomState(29)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (6, 9, 7, 11, 5, 8)]

    def drain(eng):
        reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        while not (eng.idle() and not eng.backlog):
            eng.admit([])
            eng.decode_once()
        for r in reqs:
            r.wait(timeout=120)

    def timed(profile):
        eng = DecodeEngine(m, capacity=4, s_max=64, chunk=4,
                           block_size=8,
                           profile=True if profile else None)
        drain(eng)                 # warmup: compiles + caches
        t0 = time.perf_counter()
        drain(eng)
        return time.perf_counter() - t0

    off, on = [], []
    for _ in range(5):             # interleaved, never back-to-back
        off.append(timed(False))
        on.append(timed(True))
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"profiler overhead {100 * (ratio - 1):.2f}% >= 5% "
        f"(on={min(on):.4f}s off={min(off):.4f}s)")


def test_env_flag_tolerant(monkeypatch):
    for v, want in [("1", True), ("true", True), ("YES", True),
                    ("0", False), ("", False), ("false", False)]:
        monkeypatch.setenv("BENCH_ALLOW_CPU", v)
        assert bench._env_flag("BENCH_ALLOW_CPU") is want
    monkeypatch.delenv("BENCH_ALLOW_CPU")
    assert bench._env_flag("BENCH_ALLOW_CPU") is False


_ROOT = os.path.dirname(bench.__file__)


def _run_script(args, cwd=_ROOT):
    """Run a script on a forced CPU with a clean bench/cache environment;
    ``paddle_tpu`` is importable from the checkout whatever the cwd."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_ALLOW_CPU", "BENCH_PRESET",
                        "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [_ROOT] + [p for p in [env.get("PYTHONPATH")] if p]))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=170)


def test_chip_smoke_fails_without_accelerator():
    """The contract's first clause: on a forced CPU the script exits
    non-zero and prints no result."""
    r = _run_script([os.path.join(_ROOT, "chip_smoke.py")])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """...and so it does alone in a directory, without the program."""
    import shutil
    shutil.copy(os.path.join(_ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=170)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_bench_fails_on_cpu_without_allow_flag():
    """No accelerator and no BENCH_ALLOW_CPU: an error and a non-zero
    exit code, not a toy-size number and not an rc=0 skip line."""
    r = _run_script([bench.__file__])
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "BENCH_ALLOW_CPU" in r.stderr


def test_require_accelerator_allows_intentional_cpu(monkeypatch):
    monkeypatch.setenv("BENCH_ALLOW_CPU", "1")
    assert bench.require_accelerator().platform == "cpu"
    info = bench.device_info()
    assert info["platform"] == "cpu" and info["device_count"] >= 1
    assert info["device_kind"]


def test_peak_flops_raises_on_unknown_kind(monkeypatch):
    import jax

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert bench.peak_flops_per_chip() == 197e12
    _Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="v9 imaginary"):
        bench.peak_flops_per_chip()
    _Dev.device_kind = "cpu"
    with pytest.raises(ValueError):
        bench.peak_flops_per_chip()


_CACHE_PROBE = (
    "import jax\n"
    "from paddle_tpu.utils.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def test_compile_cache_default_is_in_checkout_from_any_cwd(tmp_path):
    """Unset: ``<checkout>/.jax_cache`` resolved from the helper's own
    file, the same from two working directories, with no temporary
    name, pid or time in it."""
    outs = [_run_script(["-c", _CACHE_PROBE], cwd=cwd).stdout.split()
            for cwd in (_ROOT, str(tmp_path))]
    assert outs[0] == outs[1]
    returned, configured, floor = outs[0]
    assert returned == configured == os.path.join(_ROOT, ".jax_cache")
    assert float(floor) == 0.0


def test_compile_cache_leaves_env_dir_alone(tmp_path, monkeypatch):
    """Set from outside: JAX reads the variable itself and the helper
    sets no other directory."""
    import jax
    from paddle_tpu.utils import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append(k))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in calls
    assert "jax_persistent_cache_min_compile_time_secs" in calls


def test_compile_cache_dir_is_set_in_one_place():
    """``jax_compilation_cache_dir`` is assigned in exactly one place in
    the tree (and never from tempfile, a pid or a clock)."""
    hits = []
    for base, dirs, files in os.walk(_ROOT):
        dirs[:] = [d for d in dirs
                   if d not in (".git", "__pycache__", ".jax_cache",
                                "chiprun_out", "log", "tests")]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                if '"jax_compilation_cache_dir"' in open(path).read():
                    hits.append(os.path.relpath(path, _ROOT))
    assert hits == [os.path.join("paddle_tpu", "utils",
                                 "compile_cache.py")]


def test_set_device_raises_without_accelerator():
    """``set_device("tpu")`` on a CPU-only host is an error, as is an
    index past the last device; neither is clamped to something that
    exists."""
    import paddle_tpu as paddle
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no accelerator"):
        paddle.set_device("gpu:0")
    with pytest.raises(ValueError, match="out of range"):
        paddle.set_device("cpu:99")
    assert paddle.set_device("cpu:0").platform == "cpu"

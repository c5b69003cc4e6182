"""Timer-discipline lint (ISSUE 3 satellite, extended by ISSUE 5,
ported to graftcheck by ISSUE 11): serving code must stamp time through
``paddle_tpu.observability.now`` — the one clock the metrics registry,
request traces, and engine spans share — never via ad-hoc
``time.perf_counter()`` pairs. A raw call sneaking back into the
inference package would let a hand-rolled latency number disagree with
the trace-derived histograms, which is exactly the drift the
observability layer exists to end.

ISSUE 5 widened the net to the observability package itself and the
stall watchdog: those modules DEFINE and CONSUME the shared clock, so
they are additionally banned from ``time.monotonic`` (the watchdog's
old clock) — everything goes through ``observability.now``. The single
exemption is the alias-definition line in ``observability/metrics.py``
(``now = time.perf_counter``), which is the one place the raw spelling
is the point.

ISSUE 11: the scan logic lives in
:class:`paddle_tpu.staticcheck.timers.AdhocTimerChecker` (SC01) and
the scan-set lists in :mod:`paddle_tpu.staticcheck.config`; this file
is a thin wrapper that keeps the historic test names (and therefore
the historic CI gate) alive. Byte-equivalence of the verdicts against
the pre-port lint is asserted in ``tests/test_staticcheck.py``.
"""

from paddle_tpu.staticcheck import AdhocTimerChecker, run
from paddle_tpu.staticcheck.config import (WATCHDOG,
                                           timer_inference_paths,
                                           timer_model_paths,
                                           timer_shared_clock_paths)


def test_inference_package_has_no_raw_perf_counter():
    res = run(sources=timer_inference_paths(),
              checkers=[AdhocTimerChecker])
    assert res.ok, (
        "raw time.perf_counter() in paddle_tpu/inference/ — use "
        "`from ..observability import now` instead:\n"
        + "\n".join(f.render() for f in res.findings))


def test_observability_and_watchdog_use_shared_clock():
    """ISSUE 5: the telemetry substrate itself must not fork the clock
    — observability/ and the stall watchdog are banned from BOTH raw
    spellings (perf_counter AND the watchdog's old monotonic), modulo
    the alias-definition line in metrics.py."""
    res = run(sources=timer_shared_clock_paths(),
              checkers=[AdhocTimerChecker])
    assert res.ok, (
        "raw timer call in observability/ or distributed/watchdog.py "
        "— use `observability.now`:\n"
        + "\n".join(f.render() for f in res.findings))


def test_lint_covers_fleet_modules():
    """ISSUE 4 grew the package by fleet.py/fleet_metrics.py and
    ISSUE 6 by qos.py/traffic.py; ISSUE 7's chunked prefill rides
    inside serving.py/scheduler.py/qos.py, ISSUE 8 added spec_decode.py
    (the n-gram drafter must stay pure — a wall clock in the draft path
    would de-determinize the verify oracle), ISSUE 9 added chaos.py
    (the fault schedule's clock is the fleet STEP INDEX), and ISSUE 10
    added sharding.py (mesh/spec construction is pure wiring), so those
    staying in the scan set keeps their timing under the lint too. The
    config group must actually be scanning them (a rename or package
    move would silently shrink the lint's coverage). QoS/traffic in
    particular must never grow a wall clock — their determinism
    contract is injected clocks only."""
    scanned = {p.name for p in timer_inference_paths()}
    for required in ("serving.py", "fleet.py", "fleet_metrics.py",
                     "prefix_cache.py", "scheduler.py", "qos.py",
                     "traffic.py", "spec_decode.py", "chaos.py",
                     "sharding.py"):
        assert required in scanned, (
            f"{required} missing from the timer-lint scan set "
            f"{sorted(scanned)}")


def test_served_models_and_kernels_have_no_raw_timer():
    """The families the engine binds programs from (``llama.py``,
    ``granite_hybrid.py``) and the kernels they launch
    (``ssm_update.py`` among them) are under the lint too."""
    paths = timer_model_paths()
    scanned = {p.name for p in paths}
    for required in ("llama.py", "granite_hybrid.py", "ssm_update.py",
                     "paged_attention.py"):
        assert required in scanned and \
            all(p.exists() for p in paths), sorted(scanned)
    res = run(sources=paths, checkers=[AdhocTimerChecker])
    assert res.ok, "\n".join(f.render() for f in res.findings)


def test_lint_covers_observability_modules():
    """ISSUE 5 grew observability/ by slo.py/export.py; the widened
    scan set must include them and the watchdog."""
    scanned = {p.name for p in timer_shared_clock_paths()}
    for required in ("metrics.py", "tracing.py", "slo.py", "export.py"):
        assert required in scanned, (
            f"{required} missing from the observability lint scan set "
            f"{sorted(scanned)}")
    assert WATCHDOG.exists(), "distributed/watchdog.py moved"


def test_shared_clock_is_perf_counter():
    """The alias must BE the high-resolution monotonic clock (the lint
    bans the spelling, not the clock)."""
    import time

    from paddle_tpu.observability import now
    assert now is time.perf_counter

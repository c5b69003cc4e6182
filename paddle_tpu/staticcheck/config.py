"""graftcheck scan-set configuration (ISSUE 11): the ONE place that
says which files the serving stack's invariants are enforced on. The
two pre-framework lints each carried a private copy of this list; the
rewritten ``tests/test_no_adhoc_timers.py`` / ``test_no_silent_except.py``
now import these groups instead of globbing on their own.

Groups:

- :func:`scan_paths` — the full shared scan set every SC03+ checker
  sees: ``paddle_tpu/inference/``, ``paddle_tpu/observability/``,
  ``paddle_tpu/distributed/watchdog.py``, ``paddle_tpu/models/llama.py``
  and the families' seam ``paddle_tpu/models/paged_stack.py``,
  ``paddle_tpu/kernels/`` and ``bench.py``;
- :func:`timer_inference_paths` / :func:`timer_shared_clock_paths` —
  SC01's two historic tiers (inference/ bans ``time.perf_counter``;
  the clock-owning observability/ + watchdog additionally ban
  ``time.monotonic``, modulo the alias-definition line);
- :func:`silent_except_paths` — SC02's tier (inference/ +
  observability/, the packages whose broad handlers must be loud);
- :func:`nondet_extra_paths` — the serving TEST harnesses (ISSUE 12
  satellite): conftest/launch_worker and the serving-stack test files
  whose seeded-replay discipline SC04 now also enforces (and whose
  metric-name assertions SC08 resolves against the registrations);
- :func:`run_paths` — the default CLI run set: scan set + the SC04
  test group.

ISSUE 12 also parks the interprocedural checkers' tables here:
:data:`BUCKET_HELPERS` (SC06's sanctioned bucketing functions) and
:data:`STEP_PATH_ROOTS` (SC07's reachability roots).
"""

from __future__ import annotations

import pathlib

__all__ = ["REPO_ROOT", "PKG", "scan_paths", "timer_inference_paths",
           "timer_shared_clock_paths", "silent_except_paths",
           "nondet_extra_paths", "run_paths",
           "WATCHDOG", "TRACED_EXTRA_NAMES", "BUCKET_HELPERS",
           "STEP_PATH_ROOTS", "is_external",
           "in_timer_inference", "in_timer_shared_clock",
           "in_silent_except", "in_nondet_extra", "in_scan_set"]

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
PKG = REPO_ROOT / "paddle_tpu"
WATCHDOG = PKG / "distributed" / "watchdog.py"

#: SC03 fallback: functions the engine stores in its compiled-program
#: caches whose jit wrapping the AST walk cannot see lexically (the
#: factory call happens behind an attribute alias). The factory
#: resolver in host_sync.py catches today's tree on its own; this list
#: exists so a refactor that breaks the lexical chain can pin the
#: traced names explicitly instead of silently dropping coverage.
TRACED_EXTRA_NAMES: frozenset = frozenset()

#: SC06: functions that map a request-derived Python int into the
#: finite bucket domain the compiled-program caches are keyed on (the
#: engine's windows/bucket table). A value that passed through one of
#: these is sanctioned as a jit cache key.
BUCKET_HELPERS: frozenset = frozenset({"_bucket_window", "_bucket_len",
                                       "_bucket_pages"})

#: SC07: reachability roots of the serving hot path. Resolved against
#: the call graph by display name; roots that resolve to nothing are
#: skipped (``DecodeEngine.step`` is listed for the RPC-fleet arc even
#: though today's engine only has ``decode_once``).
STEP_PATH_ROOTS: tuple = ("ServingFleet.step", "DecodeEngine.step",
                          "DecodeEngine.decode_once")

#: ISSUE 13: observability modules the scan set must always contain.
#: The flight recorder / step profiler carry their own lock-discipline
#: and clock-alias invariants (SC01/SC05); a rename that silently drops
#: them from the glob would un-enforce those. ``scan_paths`` asserts
#: their presence on every build of the set.
OBSERVABILITY_PINNED: tuple = ("flight.py", "profiling.py", "dump.py")

#: ISSUE 14: inference modules the scan set must always contain. The
#: KV migration path mutates BOTH endpoints' allocators and donates a
#: pool — exactly the territory SC06 (bucketed launch shapes) and SC09
#: (donation rebind, live source operand) exist for. Same rule as the
#: observability pins: dropping it from the glob must fail the build.
INFERENCE_PINNED: tuple = ("migration.py",)


def _glob(d: pathlib.Path) -> list[pathlib.Path]:
    return sorted(p for p in d.glob("*.py") if p.name != "__pycache__")


def timer_inference_paths() -> list[pathlib.Path]:
    return _glob(PKG / "inference")


def timer_model_paths() -> list[pathlib.Path]:
    """The model families the engine serves and their kernels: what
    they compute rides inside the engine's spans, so a wall clock of
    their own would fork the one the traces share."""
    return [PKG / "models" / "llama.py",
            PKG / "models" / "paged_stack.py",
            PKG / "models" / "granite_hybrid.py",
            PKG / "models" / "mimo_v2.py"] + _glob(PKG / "kernels")


def timer_shared_clock_paths() -> list[pathlib.Path]:
    return _glob(PKG / "observability") + [WATCHDOG]


def silent_except_paths() -> list[pathlib.Path]:
    return _glob(PKG / "inference") + _glob(PKG / "observability")


def scan_paths() -> list[pathlib.Path]:
    """The full shared scan set, deterministic order. Asserts the
    ISSUE 13 observability modules are present — a rename that drops
    them from the glob must fail the build, not quietly narrow the
    checked set."""
    paths = (
        _glob(PKG / "inference")
        + _glob(PKG / "observability")
        + [WATCHDOG]
        + [PKG / "models" / "llama.py",
           PKG / "models" / "paged_stack.py",
           PKG / "models" / "granite_hybrid.py",
           PKG / "models" / "mimo_v2.py"]
        + _glob(PKG / "kernels")
        + [REPO_ROOT / "bench.py"]
    )
    names = {p.name for p in paths}
    missing = [n for n in OBSERVABILITY_PINNED if n not in names]
    if missing:
        raise AssertionError(
            f"pinned observability modules missing from scan set: "
            f"{missing} (OBSERVABILITY_PINNED)")
    missing = [n for n in INFERENCE_PINNED if n not in names]
    if missing:
        raise AssertionError(
            f"pinned inference modules missing from scan set: "
            f"{missing} (INFERENCE_PINNED)")
    return paths


#: The serving-stack test harnesses SC04 (and SC08's asserted-name
#: resolution) additionally cover. test_staticcheck.py is deliberately
#: absent: its embedded fixture STRINGS contain suppression directives
#: that the raw-line directive scan would misread as the file's own.
_NONDET_EXTRA = (
    "conftest.py", "launch_worker.py", "test_fleet.py", "test_qos.py",
    "test_chaos.py", "test_slo.py", "test_spec_decode.py",
    "test_chunked_prefill.py", "test_prefix_scheduler.py",
    "test_observability.py", "test_paged_attention.py",
    "test_tp_sharding.py", "test_bench_probe.py", "test_migration.py",
    "test_seq_parallel.py")


def nondet_extra_paths() -> list[pathlib.Path]:
    """The seeded-replay test group (ISSUE 12 satellite), deterministic
    order."""
    return [REPO_ROOT / "tests" / n for n in _NONDET_EXTRA]


def run_paths() -> list[pathlib.Path]:
    """Everything the default CLI invocation scans."""
    return scan_paths() + nondet_extra_paths()


def _src_rpath(src):
    """``src.path.resolve()`` memoized on the SourceFile — group
    predicates run once per (checker, file) and pathlib resolution
    dominated the 9-checker CLI profile before this cache."""
    rp = getattr(src, "_rpath", None)
    if rp is None and src.path is not None:
        rp = src.path.resolve()
        src._rpath = rp
    return rp


def is_external(src) -> bool:
    """True for an explicit CLI path OUTSIDE the repository (e.g. a
    test fixture in a temp dir) — such files get every checker's
    widest net, like virtual fixtures."""
    if src.virtual or src.path is None:
        return False
    ext = getattr(src, "_external", None)
    if ext is None:
        try:
            _src_rpath(src).relative_to(REPO_ROOT)
            ext = False
        except ValueError:
            ext = True
        src._external = ext
    return ext


#: key -> frozenset of resolved group paths (the groups are static
#: per process; re-globbing + re-resolving per predicate call was the
#: CLI's hottest path)
_GROUP_CACHE: dict = {}


def _group_set(key, paths_fn):
    got = _GROUP_CACHE.get(key)
    if got is None:
        got = frozenset(p.resolve() for p in paths_fn())
        _GROUP_CACHE[key] = got
    return got


def _under(src, group) -> bool:
    """True when ``src`` (a SourceFile) is one of ``group``'s paths —
    virtual fixture sources and external CLI paths always match, so
    tests can drive any checker with embedded snippets or temp
    files."""
    if src.virtual or is_external(src):
        return True
    rp = _src_rpath(src)
    return rp is not None and rp in {p.resolve() for p in group}


def _under_key(src, key, paths_fn) -> bool:
    if src.virtual or is_external(src):
        return True
    rp = _src_rpath(src)
    return rp is not None and rp in _group_set(key, paths_fn)


def _in_repo_key(src, key, paths_fn) -> bool:
    return (not src.virtual and not is_external(src)
            and _under_key(src, key, paths_fn))


def in_timer_inference(src) -> bool:
    return _in_repo_key(src, "timer_inf", timer_inference_paths)


def in_timer_shared_clock(src) -> bool:
    return _in_repo_key(src, "timer_clock", timer_shared_clock_paths)


def in_silent_except(src) -> bool:
    return _under_key(src, "silent_except", silent_except_paths)


def in_scan_set(src) -> bool:
    """The default checker group: the shared scan set (virtual
    fixtures and external CLI paths always pass)."""
    return _under_key(src, "scan", scan_paths)


def in_nondet_extra(src) -> bool:
    """True only for REAL files of the test-harness group — virtual/
    external fixtures already pass every group via
    :func:`in_scan_set`."""
    return _in_repo_key(src, "nondet_extra", nondet_extra_paths)

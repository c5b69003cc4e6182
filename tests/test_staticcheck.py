"""graftcheck framework tests (ISSUE 11 tentpole + ISSUE 12 call-graph
layer): per-checker positive/negative fixtures driven through embedded
source strings (no temp files — ``SourceFile.from_source`` parses in
memory), suppression and unused-suppression behavior, CLI ``--json`` /
``--format=github`` shape, byte-equivalence of the SC01/SC02 ports
against inline reimplementations of the pre-framework lints, callgraph
resolution/reachability units, and the zero-findings gate over the real
scan set at HEAD.
"""

import ast
import json

import pytest

from paddle_tpu.staticcheck import (AdhocTimerChecker, CallGraph,
                                    DonationDisciplineChecker, Finding,
                                    HostSyncChecker,
                                    LockDisciplineChecker,
                                    MetricsSchemaChecker,
                                    RecompileHazardChecker, SourceFile,
                                    SilentExceptChecker,
                                    StepPathBlockingChecker,
                                    UNUSED_SUPPRESSION_ID,
                                    UnseededRandomChecker,
                                    all_checker_classes, checker_by_id,
                                    run)
from paddle_tpu.staticcheck.__main__ import expand_checker_ids
from paddle_tpu.staticcheck.__main__ import main as cli_main
from paddle_tpu.staticcheck import callgraph, config, host_sync, util

pytestmark = pytest.mark.staticcheck


def _check(checker_cls, text, name="fx.py"):
    """Raw checker findings over an embedded fixture (no suppression
    layer — that is run()'s job and tested separately)."""
    src = SourceFile.from_source(name, text)
    return list(checker_cls().check(src))


def _lines(findings):
    return sorted(f.line for f in findings)


# -- core: findings, registry, directives -----------------------------------

def test_finding_order_is_file_line_checker_message():
    fs = [Finding("b.py", 1, "SC02", "m"),
          Finding("a.py", 9, "SC05", "m"),
          Finding("a.py", 2, "SC03", "z"),
          Finding("a.py", 2, "SC03", "a")]
    assert sorted(fs) == [Finding("a.py", 2, "SC03", "a"),
                          Finding("a.py", 2, "SC03", "z"),
                          Finding("a.py", 9, "SC05", "m"),
                          Finding("b.py", 1, "SC02", "m")]
    assert fs[0].render() == "b.py:1: SC02 m"


def test_registry_has_the_nine_checkers():
    ids = [c.id for c in all_checker_classes()]
    assert ids == ["SC01", "SC02", "SC03", "SC04", "SC05",
                   "SC06", "SC07", "SC08", "SC09"]
    assert checker_by_id("SC03") is HostSyncChecker
    assert checker_by_id("SC07") is StepPathBlockingChecker
    with pytest.raises(KeyError):
        checker_by_id("SC99")
    # the interprocedural layer is explicit about which checkers ride
    # the shared CallGraph
    proj = {c.id for c in all_checker_classes() if c.project}
    assert proj == {"SC07", "SC08"}


def test_sourcefile_parses_comment_directives():
    src = SourceFile.from_source("d.py", (
        "x = 1  # staticcheck: disable=SC04, SC05\n"
        "self._m = {}   # guarded-by: _lock\n"
        "def f(self):   # staticcheck: holds=_mu\n"
        "    pass\n"))
    assert src.suppressions == {1: {"SC04", "SC05"}}
    assert src.guarded_by == {2: "_lock"}
    assert src.holds == {3: "_mu"}
    assert src.virtual


# -- SC01 no-adhoc-timers ---------------------------------------------------

def test_sc01_flags_both_spellings_and_exempts_alias_def():
    fs = _check(AdhocTimerChecker, (
        "t0 = time.perf_counter()\n"
        "t1 = time.monotonic()\n"
        "now = time.perf_counter\n"       # the alias definition itself
        "dt = now() - t0\n"))
    assert _lines(fs) == [1, 2]
    assert all(f.checker_id == "SC01" for f in fs)
    assert "observability.now" in fs[0].message


def test_sc01_inference_tier_allows_monotonic():
    """The historic two-tier rule: inference/ bans only perf_counter;
    observability/+watchdog ban monotonic too."""
    chk = AdhocTimerChecker()
    serving = config.PKG / "inference" / "serving.py"
    src = SourceFile.from_path(serving, config.REPO_ROOT)
    banned, allow_alias = chk._banned(src)
    assert banned == ("time.perf_counter",) and not allow_alias
    metrics = config.PKG / "observability" / "metrics.py"
    src = SourceFile.from_path(metrics, config.REPO_ROOT)
    banned, allow_alias = chk._banned(src)
    assert banned == ("time.perf_counter", "time.monotonic")
    assert allow_alias


# -- SC02 no-silent-except --------------------------------------------------

def test_sc02_flags_silent_and_exempts_loud_and_narrow():
    fs = _check(SilentExceptChecker, (
        "try:\n"
        "    pass\n"
        "except ValueError:\n"
        "    pass\n"                       # narrow: exempt
        "except Exception:\n"
        "    pass\n"                       # broad + silent: finding (5)
        "try:\n"
        "    pass\n"
        "except Exception as e:\n"
        "    log_kv(_log, 'x', err=e)\n"   # loud: exempt
        "try:\n"
        "    pass\n"
        "except BaseException:\n"
        "    raise\n"                      # re-raise: exempt
        "try:\n"
        "    pass\n"
        "except Exception:\n"
        "    self._c_errors.inc()\n"       # error counter: exempt
        "try:\n"
        "    pass\n"
        "except Exception:\n"
        "    req.error = 'boom'\n"))       # surfaced on request: exempt
    assert _lines(fs) == [5]
    assert fs[0].checker_id == "SC02"


def test_sc02_records_examined_handlers():
    chk = SilentExceptChecker()
    src = SourceFile.from_source("h.py", (
        "try:\n    pass\nexcept Exception:\n    raise\n"
        "try:\n    pass\nexcept KeyError:\n    pass\n"))
    assert not list(chk.check(src))
    assert chk.broad_handlers == [("h.py", 3)]   # narrow not recorded


# -- SC03 host-sync-in-traced-code ------------------------------------------

SC03_FIXTURE = """\
import jax, functools
import numpy as np

def step(tok, lens):
    if lens > 0:                 # finding: dynamic `if`
        x = float(tok)           # finding: host cast
    y = tok.item()               # finding: device->host copy
    z = np.asarray(lens)         # finding: host materialization
    if tok is None:              # exempt: identity test
        pass
    if tok.shape[0] > 1:         # exempt: trace-static attr
        pass
    if len(lens) > 2:            # exempt: trace-static call
        pass
    return tok

prog = jax.jit(step)
"""


def test_sc03_flags_host_syncs_in_jitted_function():
    fs = _check(HostSyncChecker, SC03_FIXTURE)
    assert _lines(fs) == [5, 6, 7, 8]
    assert all("'step'" in f.message for f in fs)


def test_sc03_untraced_function_is_exempt():
    fs = _check(HostSyncChecker, (
        "def plain(a):\n"
        "    if a:\n"
        "        return float(a)\n"
        "    return 0\n"))
    assert fs == []


def test_sc03_decorator_forms():
    fs = _check(HostSyncChecker, (
        "import jax, functools\n"
        "@jax.jit\n"
        "def f(a):\n"
        "    return bool(a)\n"             # finding (4)
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def g(x, n):\n"
        "    if n:\n"                      # exempt: static_argnames
        "        pass\n"
        "    assert x\n"                   # finding (9)
        "@jax.jit\n"
        "def h(x, m):\n"
        "    return x if m else -x\n"))    # finding (12): ternary
    assert _lines(fs) == [4, 9, 12]


def test_sc03_static_argnums_and_partial_positionals():
    fs = _check(HostSyncChecker, (
        "import jax, functools\n"
        "def gen(cfg, n, x):\n"
        "    if n > 1:\n"                  # exempt: partial-bound
        "        pass\n"
        "    while x:\n"                   # finding (5)
        "        break\n"
        "f = jax.jit(functools.partial(gen, None, 5))\n"
        "def k(a, b):\n"
        "    return a and b\n"             # finding (9), b only
        "g = jax.jit(k, static_argnums=(0,))\n"))
    assert _lines(fs) == [5, 9]
    msgs = "\n".join(f.message for f in fs)
    assert "'x'" in msgs and "'b'" in msgs and "'a'" not in msgs


def test_sc03_factory_returned_program_is_traced():
    fs = _check(HostSyncChecker, (
        "import jax\n"
        "def make_decode(n):\n"
        "    def decode_chunk(state, tok):\n"
        "        if tok:\n"                # finding (4)
        "            return state\n"
        "        return state\n"
        "    return decode_chunk\n"
        "prog = jax.jit(make_decode(4))\n"))
    assert _lines(fs) == [4]
    assert "'decode_chunk'" in fs[0].message


def test_sc03_pallas_partial_kernel_and_control_hofs():
    fs = _check(HostSyncChecker, (
        "import functools\n"
        "from jax.experimental import pallas as pl\n"
        "import jax.lax as lax\n"
        "def _kern(q_ref, o_ref, *, bs):\n"
        "    if bs:\n"                     # exempt: partial kwarg
        "        pass\n"
        "    if q_ref:\n"                  # finding (7)
        "        pass\n"
        "kernel = functools.partial(_kern, bs=8)\n"
        "pl.pallas_call(kernel)\n"
        "def body(carry, x):\n"
        "    assert carry\n"               # finding (12)
        "    return carry, x\n"
        "lax.scan(body, 0, None)\n"))
    assert _lines(fs) == [7, 12]


def test_sc03_attribute_alias_to_factory():
    fs = _check(HostSyncChecker, (
        "import jax\n"
        "def make_prefill(sc):\n"
        "    def prefill(ids, lm):\n"
        "        if lm is None:\n"         # exempt: identity
        "            lm = ids\n"
        "        return ids.tolist()\n"    # finding (6)
        "    return prefill\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._make_prefill = make_prefill\n"
        "    def compile(self, sc):\n"
        "        return jax.jit(self._make_prefill(sc))\n"))
    assert _lines(fs) == [6]


# -- SC04 unseeded-nondeterminism -------------------------------------------

def test_sc04_global_rng_and_unseeded_constructors():
    fs = _check(UnseededRandomChecker, (
        "import random\n"
        "import numpy as np\n"
        "r = random.random()\n"            # finding
        "random.shuffle(items)\n"          # finding
        "g = np.random.default_rng()\n"    # finding: unseeded ctor
        "h = np.random.default_rng(0)\n"   # exempt: seeded
        "k = np.random.rand(3)\n"          # finding
        "ok = random.Random(42)\n"         # exempt: seeded ctor
        "m = rng.random()\n"               # exempt: instance method
        "j = jax.random.normal(key)\n"))   # exempt: key-based
    assert _lines(fs) == [3, 4, 5, 7]


def test_sc04_set_iteration():
    fs = _check(UnseededRandomChecker, (
        "for x in {1, 2}:\n"               # finding
        "    pass\n"
        "for y in set(items):\n"           # finding
        "    pass\n"
        "l = list({v for v in vs})\n"      # finding
        "ok = sorted(set(items))\n"        # exempt: sorted
        "for z in [1, 2]:\n"               # exempt: list
        "    pass\n"
        "d = [k for k in set(ws)]\n"))     # finding
    assert _lines(fs) == [1, 3, 5, 9]


# -- SC05 lock-discipline ---------------------------------------------------

SC05_FIXTURE = """\
import threading

class Reg:
    def __init__(self):
        self._m = {}                       # guarded-by: _lock
        self._lock = threading.Lock()
    def get(self, k):
        return self._m.get(k)              # finding (8): read
    def put(self, k, v):
        with self._lock:
            self._m[k] = v                 # ok: lock held
    def clear(self):
        self._m = {}                       # finding (13): write
    def _sweep_locked(self):
        return len(self._m)                # ok: _locked suffix
    def peek(self, k):                     # staticcheck: holds=_lock
        return self._m[k]                  # ok: caller-holds contract
    def bind(self):
        return lambda: len(self._m)        # finding (19): deferred
    def other(self):
        return self._unrelated             # ok: not guarded
"""


def test_sc05_guarded_attr_accesses():
    fs = _check(LockDisciplineChecker, SC05_FIXTURE)
    assert _lines(fs) == [8, 13, 19]
    by_line = {f.line: f.message for f in fs}
    assert by_line[8].startswith("read of '_m'")
    assert by_line[13].startswith("write of '_m'")
    assert "bind()" in by_line[19]


def test_sc05_nested_function_does_not_inherit_lock():
    """A closure created INSIDE a with-lock block runs later (gauge
    callbacks run on the scrape thread) with no lock held — the bug
    class the fleet's fn-gauges actually had."""
    fs = _check(LockDisciplineChecker, (
        "class G:\n"
        "    def __init__(self):\n"
        "        self._n = 0            # guarded-by: _lock\n"
        "        self._lock = object()\n"
        "    def install(self, reg):\n"
        "        with self._lock:\n"
        "            reg.gauge('d', fn=lambda: self._n)\n"))
    assert _lines(fs) == [7]


def test_sc05_no_annotations_no_findings():
    fs = _check(LockDisciplineChecker, (
        "class P:\n"
        "    def __init__(self):\n"
        "        self._m = {}\n"
        "    def get(self, k):\n"
        "        return self._m.get(k)\n"))
    assert fs == []


# -- callgraph: resolution, edges, reachability (ISSUE 12 tentpole) ---------

CG_FIXTURE = """\
import jax

def make_decode(n):
    def decode_chunk(state):
        return state
    return decode_chunk

def helper(x):
    return x

class Engine:
    def __init__(self):
        self._make_decode = make_decode
    def compile(self, n):
        return jax.jit(self._make_decode(n))
    def step(self, q):
        self.tick()
        return helper(q)
    def tick(self):
        pass

def drive(e):
    e.step(None)
    w = Engine()
    return w
"""


def _graph(text, name="g.py"):
    return CallGraph([SourceFile.from_source(name, text)])


def test_callgraph_symbol_table_and_lookup():
    g = _graph(CG_FIXTURE)
    displays = {i.display for i in g.functions.values()}
    assert {"make_decode", "make_decode.decode_chunk", "helper",
            "Engine.__init__", "Engine.compile", "Engine.step",
            "Engine.tick", "drive"} <= displays
    (step,) = g.lookup("Engine.step")
    assert step.name == "step" and step.cls == "Engine"
    # bare-name fallback for plain identifiers
    assert [i.display for i in g.lookup("helper")] == ["helper"]


def test_callgraph_edges_self_import_and_ctor():
    g = _graph(CG_FIXTURE)

    def targets(display):
        (info,) = g.lookup(display)
        return {g.functions[q].display for q in g.edges[info.qualname]}

    # self.tick() binds to the OWN class's method; helper() lexically
    assert targets("Engine.step") == {"Engine.tick", "helper"}
    # attribute alias + factory: jit(self._make_decode(n)) resolves
    # through the alias to the factory AND to the def it returns
    assert {"make_decode", "make_decode.decode_chunk"} <= \
        targets("Engine.compile")
    # obj.m() over-approximates to every project fn named m, and
    # Cls(...) adds the Cls.__init__ edge
    assert {"Engine.step", "Engine.__init__"} <= targets("drive")


def test_callgraph_reachability_and_paths():
    g = _graph(CG_FIXTURE)
    reach = {i.display for i in g.reachable_from("drive")}
    assert {"drive", "Engine.step", "Engine.tick", "helper",
            "Engine.__init__"} <= reach
    chains = {info.display: chain
              for info, chain in g.paths_from("drive")}
    assert chains["drive"] == ("drive",)
    assert chains["Engine.tick"] == \
        ("drive", "Engine.step", "Engine.tick")
    # a cut prunes the node AND everything only reachable through it
    cut_reach = {i.display for i in g.reachable_from(
        "drive", cut=lambda i: i.display == "Engine.step")}
    assert "Engine.step" not in cut_reach
    assert "Engine.tick" not in cut_reach


def test_callgraph_callers_of():
    g = _graph(CG_FIXTURE)
    assert [i.display for i in g.callers_of("Engine.tick")] == \
        ["Engine.step"]
    assert "drive" in {i.display for i in g.callers_of("Engine.step")}


def test_callgraph_import_edge_across_files():
    a = SourceFile.from_source("pkg/alpha.py",
                               "def shared_helper(x):\n    return x\n")
    b = SourceFile.from_source("pkg/beta.py", (
        "from pkg.alpha import shared_helper\n"
        "def use(q):\n"
        "    return shared_helper(q)\n"))
    g = CallGraph([a, b])
    (use,) = g.lookup("use")
    assert [g.functions[q].display for q in g.edges[use.qualname]] == \
        ["shared_helper"]


def test_callgraph_is_deterministic():
    def build():
        srcs = [SourceFile.from_source("g.py", CG_FIXTURE),
                SourceFile.from_source("pkg/alpha.py",
                                       "def shared_helper(x):\n"
                                       "    return x\n")]
        return CallGraph(srcs)
    g1, g2 = build(), build()
    assert g1.edges == g2.edges
    assert list(g1.functions) == list(g2.functions)
    assert [i.qualname for i in g1.reachable_from("drive")] == \
        [i.qualname for i in g2.reachable_from("drive")]


def test_file_index_is_memoized_per_source():
    src = SourceFile.from_source("m.py", "def f():\n    pass\n")
    assert callgraph.file_index(src) is callgraph.file_index(src)


def test_sc03_rides_the_hoisted_resolver():
    """ISSUE 12 hoist regression: host_sync's resolver machinery IS
    callgraph's (aliases kept for back-compat), and SC03's verdicts
    over the real scan set are byte-identical run to run."""
    assert host_sync._Statics is callgraph.Statics
    assert host_sync._jit_statics is callgraph.jit_statics
    assert host_sync._last_name is callgraph.last_name
    assert host_sync._param_names is callgraph.param_names
    res1 = run(sources=config.scan_paths(), checkers=[HostSyncChecker])
    res2 = run(sources=config.scan_paths(), checkers=[HostSyncChecker])
    assert res1.to_json() == res2.to_json()
    assert res1.ok


# -- SC06 recompile-hazard --------------------------------------------------

SC06_FACTORY_PREFIX = """\
import jax

def _decode_for(n):
    def dec(x):
        return x
    return jax.jit(dec)

"""


def test_sc06_tainted_factory_arg():
    fs = _check(RecompileHazardChecker, SC06_FACTORY_PREFIX + (
        "def handle(req):\n"
        "    return _decode_for(len(req.tokens))\n"))
    assert _lines(fs) == [9]
    assert fs[0].checker_id == "SC06"
    assert "_decode_for" in fs[0].message
    assert "_bucket" in fs[0].message


def test_sc06_bucket_helper_sanitizes():
    fs = _check(RecompileHazardChecker, SC06_FACTORY_PREFIX + (
        "def handle(self, req):\n"
        "    w = self._bucket_window(len(req.tokens))\n"
        "    return _decode_for(w)\n"))
    assert fs == []


def test_sc06_static_argnums_position():
    fs = _check(RecompileHazardChecker, (
        "import jax\n"
        "def f(x, n):\n"
        "    return x\n"
        "g = jax.jit(f, static_argnums=(1,))\n"
        "def step(toks):\n"
        "    n = len(toks)\n"
        "    return g(toks, n)\n"))
    assert _lines(fs) == [7]
    assert "static_argnums" in fs[0].message


def test_sc06_tainted_array_shape():
    fs = _check(RecompileHazardChecker, (
        "import jax\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    return x\n"
        "g = jax.jit(f)\n"
        "def step(toks):\n"
        "    buf = np.zeros((len(toks), 4))\n"
        "    return g(buf)\n"))
    assert _lines(fs) == [8]
    assert "shape" in fs[0].message


def test_sc06_strong_update_untaints():
    fs = _check(RecompileHazardChecker, SC06_FACTORY_PREFIX + (
        "def handle(req):\n"
        "    n = len(req.tokens)\n"
        "    n = 8\n"
        "    return _decode_for(n)\n"))
    assert fs == []


def test_sc06_jnp_array_ops_do_not_carry_int_taint():
    """jnp./lax. calls RETURN arrays — building a mask from len() is
    not an int cache key (the llama.py false-positive class)."""
    fs = _check(RecompileHazardChecker, SC06_FACTORY_PREFIX + (
        "import jax.numpy as jnp\n"
        "def handle(req):\n"
        "    mask = jnp.less(jnp.arange(8), len(req.tokens))\n"
        "    return _decode_for(mask)\n"))
    assert fs == []


# -- SC07 blocking-call-on-step-path ----------------------------------------

def _sc07(text, name="fleet.py"):
    src = SourceFile.from_source(name, text)
    g = CallGraph([src])
    return list(StepPathBlockingChecker().check_project(g, [src]))


def test_sc07_sleep_reachable_from_step_root():
    fs = _sc07(
        "import time\n"
        "class ServingFleet:\n"
        "    def step(self):\n"
        "        self._drain()\n"
        "    def _drain(self):\n"
        "        time.sleep(0.1)\n")
    assert _lines(fs) == [6]
    assert fs[0].checker_id == "SC07"
    assert "time.sleep" in fs[0].message
    assert "ServingFleet.step -> ServingFleet._drain" in fs[0].message


def test_sc07_io_boundary_cuts_the_walk():
    fs = _sc07(
        "class ServingFleet:\n"
        "    def step(self):\n"
        "        self._emit()\n"
        "    def _emit(self):  # staticcheck: io-boundary\n"
        "        open('/tmp/x', 'w')\n")
    assert fs == []


def test_sc07_off_path_io_is_not_flagged():
    fs = _sc07(
        "import time\n"
        "class ServingFleet:\n"
        "    def step(self):\n"
        "        pass\n"
        "def maintenance():\n"
        "    time.sleep(5)\n")
    assert fs == []


def test_sc07_imported_sleep_and_net_roots():
    fs = _sc07(
        "from time import sleep\n"
        "import urllib.request\n"
        "class DecodeEngine:\n"
        "    def step(self):\n"
        "        sleep(1)\n"
        "        urllib.request.urlopen('http://x')\n")
    assert _lines(fs) == [5, 6]
    msgs = "\n".join(f.message for f in fs)
    assert "time.sleep" in msgs and "urllib.request.urlopen" in msgs


# -- SC08 metrics-schema ----------------------------------------------------

def _sc08(text, name="metrics.py"):
    src = SourceFile.from_source(name, text)
    g = CallGraph([src])
    return list(MetricsSchemaChecker().check_project(g, [src]))


def test_sc08_counter_suffix_discipline():
    fs = _sc08(
        "r.counter('engine_steps', 'steps completed')\n"
        "r.gauge('queue_total', 'queued requests')\n"
        "r.counter('engine_retired_total', 'retired')\n")
    assert _lines(fs) == [1, 2]
    msgs = {f.line: f.message for f in fs}
    assert "must end '_total'" in msgs[1]
    assert "must not end '_total'" in msgs[2]


def test_sc08_kind_conflict_and_help_drift():
    fs = _sc08(
        "r.counter('steps_total', 'steps')\n"
        "q.gauge('steps_total', 'steps')\n"
        "p.counter('steps_total', 'number of steps')\n")
    msgs = "\n".join(f.message for f in fs)
    assert "registered as gauge here but as counter" in msgs
    assert "help text drifts" in msgs


def test_sc08_asserted_names_resolve_and_kinds_match():
    fs = _sc08(
        "r.gauge('queue_depth', 'queued')\n"
        "v = snap['counters']['queue_depth']\n"
        "w = snap['counters']['engine_ticks_total']\n")
    msgs = {f.line: f.message for f in fs}
    assert "asserted as counter but registered as gauge" in msgs[2]
    assert "resolves to no registration" in msgs[3]


def test_sc08_histogram_aggregates_resolve_to_base():
    fs = _sc08(
        "r.histogram('step_latency', 'seconds per step')\n"
        "b = snap['histograms'].get('step_latency')\n"
        "c = snap['counters']['step_latency_count']\n")
    assert fs == []


def test_sc08_label_keys():
    fs = _sc08(
        "r.counter('drops_total', 'drops', labels={'le': '1'})\n"
        "m.add_labels({'worker': 'w0'})\n"
        "m.add_labels({'9bad': 'x'})\n")
    msgs = {f.line: f.message for f in fs}
    assert "reserved for" in msgs[1]
    assert "must not set 'worker'" in msgs[2]
    assert "not a valid" in msgs[3]


# -- SC09 donation-discipline -----------------------------------------------

def test_sc09_range_spec_must_start_at_the_vararg():
    fs = _check(DonationDisciplineChecker, (
        "import jax\n"
        "def prog(a, b, *pool):\n"
        "    return a\n"
        "f = jax.jit(prog, donate_argnums=tuple(range(1, 3)))\n"))
    assert _lines(fs) == [4]
    assert "matches no resolved callee" in fs[0].message
    assert "prog" in fs[0].message


def test_sc09_range_spec_at_vararg_is_clean():
    fs = _check(DonationDisciplineChecker, (
        "import jax\n"
        "def prog(a, b, *pool):\n"
        "    return a\n"
        "f = jax.jit(prog, donate_argnums=tuple(range(2, 2 + n)))\n"))
    assert fs == []


def test_sc09_explicit_index_off_the_arity():
    fs = _check(DonationDisciplineChecker, (
        "import jax\n"
        "def prog(a, b):\n"
        "    return a\n"
        "f = jax.jit(prog, donate_argnums=(5,))\n"
        "g = jax.jit(prog, donate_argnums=(1,))\n"))
    assert _lines(fs) == [4]


def test_sc09_use_after_donate():
    fs = _check(DonationDisciplineChecker, (
        "import jax\n"
        "def prog(a, *pool):\n"
        "    return a\n"
        "f = jax.jit(prog, donate_argnums=tuple(range(1, 3)))\n"
        "def step(x, pool):\n"
        "    out = f(x, *pool)\n"
        "    return pool\n"))
    assert _lines(fs) == [7]
    assert "read after being donated to 'f'" in fs[0].message


def test_sc09_rebind_idiom_is_clean():
    """The engine's own shape: the donated pool is rebound from the
    call's result in the SAME statement."""
    fs = _check(DonationDisciplineChecker, (
        "import jax\n"
        "def prog(a, *pool):\n"
        "    return a\n"
        "f = jax.jit(prog, donate_argnums=tuple(range(1, 3)))\n"
        "def step(x, pool):\n"
        "    out, *pool = f(x, *pool)\n"
        "    return pool\n"))
    assert fs == []


# -- suppressions and SC00 --------------------------------------------------

def test_suppression_silences_the_finding():
    src = SourceFile.from_source("s.py", (
        "import random\n"
        "r = random.random()  # staticcheck: disable=SC04\n"))
    res = run(sources=[src], checkers=[UnseededRandomChecker])
    assert res.ok and res.findings == []


def test_unused_suppression_is_a_finding():
    src = SourceFile.from_source("s.py", (
        "x = 1  # staticcheck: disable=SC04\n"))
    res = run(sources=[src], checkers=[UnseededRandomChecker])
    assert [f.checker_id for f in res.findings] == \
        [UNUSED_SUPPRESSION_ID]
    assert res.findings[0].line == 1
    assert "unused suppression: SC04" in res.findings[0].message


def test_suppression_only_silences_the_named_checker():
    src = SourceFile.from_source("s.py", (
        "import random\n"
        "r = random.random()  # staticcheck: disable=SC03\n"))
    res = run(sources=[src],
              checkers=[UnseededRandomChecker, HostSyncChecker])
    ids = sorted(f.checker_id for f in res.findings)
    # the SC04 finding survives AND the SC03 suppression is unused
    assert ids == [UNUSED_SUPPRESSION_ID, "SC04"]


def test_sc00_itself_cannot_be_suppressed():
    src = SourceFile.from_source("s.py", (
        "x = 1  # staticcheck: disable=SC00\n"))
    res = run(sources=[src], checkers=[UnseededRandomChecker])
    assert [f.checker_id for f in res.findings] == \
        [UNUSED_SUPPRESSION_ID]
    assert "cannot be suppressed" in res.findings[0].message


def test_inactive_checker_suppression_is_not_reported_stale():
    """`--checkers SC04` must not flag a SC05 suppression as unused —
    the checker simply didn't run, which is no evidence of staleness."""
    src = SourceFile.from_source("s.py", (
        "x = self._m  # staticcheck: disable=SC05\n"))
    res = run(sources=[src], checkers=[UnseededRandomChecker])
    assert res.ok


# -- the real tree ----------------------------------------------------------

def test_scan_set_is_clean_at_head():
    """The acceptance gate: every SC01–SC09 invariant holds over the
    configured scan set (plus the SC04/SC08 test-harness group), so
    the CLI exits 0 at HEAD."""
    res = run()
    assert res.ok, "\n".join(f.render() for f in res.findings)
    assert res.files_scanned == len(config.run_paths())
    assert res.files_scanned == \
        len(config.scan_paths()) + len(config.nondet_extra_paths())


def test_report_is_deterministic():
    a, b = run(), run()
    assert a.to_json() == b.to_json()
    assert [f.render() for f in a.findings] == \
        [f.render() for f in b.findings]


def test_scan_set_covers_the_stack():
    names = {p.name for p in config.scan_paths()}
    for required in ("serving.py", "qos.py", "fleet.py", "metrics.py",
                     "watchdog.py", "llama.py", "paged_attention.py",
                     "bench.py"):
        assert required in names, f"{required} fell out of the scan set"


def test_family_modules_keep_to_the_seam():
    """A model family writes its equations, its carry and its page
    write; the block walk of the cold prefill is
    ``models/paged_stack.py``'s, and what a family takes from a sibling
    are llama's kernel wrappers and plain attention reads, nothing of the
    seam (``PagedPrograms``, ``_row_pages``, ``_token_insert``)."""
    import ast
    import re
    allowed = {"_rms", "_rope", "_attention_keymask",
               "_attention_prefix_span"}
    for name in ("granite_hybrid", "mimo_v2", "glm_moe_dsa", "deepseek_v3"):
        text = (config.PKG / "models" / f"{name}.py").read_text()
        assert not re.search(r"fori_loop\(\s*first,\s*n_blocks", text) \
            and "def run_block" not in text, f"{name} walks blocks itself"
        taken = {a.name for node in ast.walk(ast.parse(text))
                 if isinstance(node, ast.ImportFrom) and node.module == "llama"
                 for a in node.names}
        assert taken <= allowed, f"{name} imports {taken - allowed} of llama"
        assert ".llama." not in text and "import llama" not in text


# -- byte-equivalence with the pre-port lints -------------------------------

def _legacy_timer_offenders(paths, banned, allow_alias_def):
    """The pre-ISSUE-11 tests/test_no_adhoc_timers.py scan, verbatim."""
    out = []
    for py in paths:
        for lineno, line in enumerate(py.read_text().splitlines(), 1):
            if allow_alias_def and \
                    line.strip() == "now = time.perf_counter":
                continue
            for token in banned:
                if token in line:
                    out.append((py.resolve(), lineno))
    return out


def test_sc01_verdicts_match_legacy_lint_byte_for_byte():
    legacy = (
        _legacy_timer_offenders(config.timer_inference_paths(),
                                ("time.perf_counter",), False)
        + _legacy_timer_offenders(config.timer_shared_clock_paths(),
                                  ("time.perf_counter",
                                   "time.monotonic"), True))
    res = run(sources=config.timer_inference_paths()
              + config.timer_shared_clock_paths(),
              checkers=[AdhocTimerChecker])
    ported = [((config.REPO_ROOT / f.file).resolve(), f.line)
              for f in res.findings]
    assert sorted(ported) == sorted(legacy)


def _legacy_broad_handlers(paths):
    """The pre-ISSUE-11 tests/test_no_silent_except.py scan, verbatim
    (classifier logic identical to util.is_broad/is_loud — asserted
    separately below)."""
    broad = {"Exception", "BaseException"}
    offenders, examined = [], []

    def names_of(node):
        if node is None:
            return []
        elts = node.elts if isinstance(node, ast.Tuple) else [node]
        out = []
        for e in elts:
            if isinstance(e, ast.Name):
                out.append(e.id)
            elif isinstance(e, ast.Attribute):
                out.append(e.attr)
        return out

    for py in paths:
        tree = ast.parse(py.read_text(), filename=str(py))
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is not None and not any(
                    n in broad for n in names_of(node.type)):
                continue
            examined.append((py.resolve(), node.lineno))
            if not util.is_loud_handler(node):
                offenders.append((py.resolve(), node.lineno))
    return offenders, examined


def test_sc02_verdicts_match_legacy_lint_byte_for_byte():
    legacy_offenders, legacy_examined = _legacy_broad_handlers(
        config.silent_except_paths())
    chk = SilentExceptChecker()
    res = run(sources=config.silent_except_paths(), checkers=[chk])
    ported = [((config.REPO_ROOT / f.file).resolve(), f.line)
              for f in res.findings]
    examined = [((config.REPO_ROOT / rel).resolve(), line)
                for rel, line in chk.broad_handlers]
    assert sorted(ported) == sorted(legacy_offenders)
    # not just the (empty-at-HEAD) verdicts: the examined-handler sets
    # must match too, or equivalence would be vacuous
    assert sorted(examined) == sorted(legacy_examined)
    assert len(legacy_examined) >= 5


# -- util unit tests (satellite: dedup'd exemption logic) -------------------

def test_util_alias_def_exemption():
    assert util.is_alias_def_line("now = time.perf_counter")
    assert util.is_alias_def_line("   now = time.perf_counter   ")
    assert not util.is_alias_def_line("now2 = time.perf_counter")
    assert not util.is_alias_def_line("now = time.monotonic")


def _handler(src_text):
    tree = ast.parse(src_text)
    return next(n for n in ast.walk(tree)
                if isinstance(n, ast.ExceptHandler))


def test_util_loudness_taxonomy():
    assert util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception:\n    raise\n"))
    assert util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception:\n    log_event('x')\n"))
    assert util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception:\n"
        "    self._c_dropped_total.inc()\n"))
    assert util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception as e:\n    req.error = e\n"))
    # a counter without an error/drop/fail hint is NOT loud
    assert not util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception:\n    self._c_steps.inc()\n"))
    assert not util.is_loud_handler(_handler(
        "try:\n    pass\nexcept Exception:\n    print('x')\n"))


def test_util_broad_classifier():
    assert util.is_broad_handler(_handler(
        "try:\n    pass\nexcept:\n    pass\n"))
    assert util.is_broad_handler(_handler(
        "try:\n    pass\nexcept (OSError, Exception):\n    pass\n"))
    assert not util.is_broad_handler(_handler(
        "try:\n    pass\nexcept OSError:\n    pass\n"))


def test_util_name_helpers():
    call = ast.parse("a.b.c(1)").body[0].value
    assert util.name_parts(call.func) == ["a", "b", "c"]
    assert util.dotted_name(call.func) == "a.b.c"
    assert util.call_target(call) == "c"


# -- CLI --------------------------------------------------------------------

def test_cli_exits_zero_at_head(capsys):
    assert cli_main([]) == 0
    out = capsys.readouterr().out
    assert "0 findings" in out


def test_cli_json_shape(capsys):
    assert cli_main(["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    assert doc["findings"] == []
    assert doc["files_scanned"] == len(config.run_paths())
    assert [c["id"] for c in doc["checkers"]] == \
        ["SC01", "SC02", "SC03", "SC04", "SC05",
         "SC06", "SC07", "SC08", "SC09"]
    assert all(set(c) == {"id", "name"} for c in doc["checkers"])


def test_cli_list_catalog(capsys):
    assert cli_main(["--list"]) == 0
    out = capsys.readouterr().out
    for cid in ("SC01", "SC02", "SC03", "SC04", "SC05",
                "SC06", "SC07", "SC08", "SC09"):
        assert cid in out


_VIOLATIONS = {
    "SC01": "t0 = time.perf_counter()\n",
    "SC02": "try:\n    pass\nexcept Exception:\n    pass\n",
    "SC03": ("import jax\n"
             "def f(x):\n"
             "    return float(x)\n"
             "g = jax.jit(f)\n"),
    "SC04": "import random\nr = random.random()\n",
    "SC05": ("class C:\n"
             "    def __init__(self):\n"
             "        self._m = {}   # guarded-by: _lock\n"
             "        self._lock = object()\n"
             "    def get(self):\n"
             "        return self._m\n"),
    "SC06": ("import jax\n"
             "def _decode_for(n):\n"
             "    def dec(x):\n"
             "        return x\n"
             "    return jax.jit(dec)\n"
             "def handle(req):\n"
             "    return _decode_for(len(req.tokens))\n"),
    "SC07": ("import time\n"
             "class ServingFleet:\n"
             "    def step(self):\n"
             "        self._drain()\n"
             "    def _drain(self):\n"
             "        time.sleep(0.1)\n"),
    "SC08": "r.counter('engine_steps', 'steps completed')\n",
    "SC09": ("import jax\n"
             "def prog(a, b, *pool):\n"
             "    return a\n"
             "f = jax.jit(prog, donate_argnums=tuple(range(1, 3)))\n"),
}

_VIOLATION_LINES = {"SC01": 1, "SC02": 3, "SC03": 3, "SC04": 2,
                    "SC05": 6, "SC06": 7, "SC07": 6, "SC08": 1,
                    "SC09": 4}


@pytest.mark.parametrize("cid", sorted(_VIOLATIONS))
def test_cli_exits_nonzero_on_violating_fixture_module(cid, tmp_path,
                                                       capsys):
    """The acceptance criterion: the CLI run against a fixture module
    violating each checker exits nonzero with a correct file:line."""
    mod = tmp_path / f"bad_{cid.lower()}.py"
    mod.write_text(_VIOLATIONS[cid])
    assert cli_main([str(mod)]) == 1
    out = capsys.readouterr().out
    want = f"{mod.resolve().as_posix()}:{_VIOLATION_LINES[cid]}: {cid} "
    assert want in out, f"missing {want!r} in:\n{out}"


def test_cli_checker_subset(tmp_path, capsys):
    mod = tmp_path / "bad.py"
    mod.write_text("import random\nr = random.random()\n"
                   "t0 = time.perf_counter()\n")
    assert cli_main([str(mod), "--checkers", "SC01"]) == 1
    out = capsys.readouterr().out
    assert "SC01" in out and "SC04" not in out
    capsys.readouterr()
    assert cli_main([str(mod), "--checkers", "SC03"]) == 0


def test_expand_checker_ids_range_syntax():
    assert expand_checker_ids("SC01,SC06-SC09") == \
        ["SC01", "SC06", "SC07", "SC08", "SC09"]
    assert expand_checker_ids("SC06-09") == \
        ["SC06", "SC07", "SC08", "SC09"]
    assert expand_checker_ids("SC03") == ["SC03"]
    with pytest.raises(ValueError):
        expand_checker_ids("SC09-SC06")


def test_cli_checker_range(tmp_path, capsys):
    mod = tmp_path / "bad.py"
    mod.write_text(_VIOLATIONS["SC09"])
    assert cli_main([str(mod), "--checkers", "SC06-SC09"]) == 1
    out = capsys.readouterr().out
    assert "SC09" in out
    capsys.readouterr()
    # the SC01-SC05 slice does not see the donation hazard
    assert cli_main([str(mod), "--checkers", "SC01-SC05"]) == 0


def test_cli_github_format(tmp_path, capsys):
    mod = tmp_path / "bad.py"
    mod.write_text(_VIOLATIONS["SC04"])
    assert cli_main([str(mod), "--format=github"]) == 1
    out = capsys.readouterr().out
    want = f"::error file={mod.resolve().as_posix()},line=2::SC04 "
    assert want in out, f"missing {want!r} in:\n{out}"
    capsys.readouterr()
    # clean tree -> no annotation lines at all
    assert cli_main(["--format=github"]) == 0
    assert capsys.readouterr().out == ""

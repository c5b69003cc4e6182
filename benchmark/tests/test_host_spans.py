"""The host-span readers: the arithmetic on a hand-built list of events
and on a slice recorded on the chip (``record_host_slice.py``)."""

import json
import pathlib

import pytest

from benchmark.lib import host_spans, layer_metrics, trace_reduce
from benchmark.lib.host_spans import HostEvent
from benchmark.lib.trace_reduce import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV, LINE = "/device:TPU:0", "/host:CPU#3"
READERS = ("host_gap_ms", "host_gap_sync_ms", "host_gap_publish_ms",
           "host_gap_poll_ms", "host_gap_launch_ms", "idle_attributed",
           "chunk_gap_p95_ms", "engine_step_span_ms")
US = 1e3


def module(name, start, dur):
    return Event(DEV, trace_reduce.MODULES_LINE, f"jit_{name}(77)", start, dur)


def op(start, dur, name="%fusion.1 = bf16[8]{0} fusion()"):
    return Event(DEV, trace_reduce.OPS_LINE, name, start, dur)


def host(name, start, end, line=LINE, **args):
    return HostEvent(line, name, start, end - start, args)


def launch(start, end, index, kind, rows=3, units=8, t_ns=None):
    return host("launch", start, end, launch=index, kind=kind, units=units,
                rows=rows, tokens=100,
                t_ns=(start - 5e9) if t_ns is None else t_ns)


def hand_built():
    """Microseconds. A prefill (launch 0) and two decode launches (1, 2)
    whole, with a decode launch cut by the trace's start before them
    (its module is there, its annotation is not).

    decode module (no annotation) 0-900
    gap A 900-1000 before the prefill: admission 900-960, launch 960-1000
    prefill module 1000-2000; its host_sync (inside admission) ends 2010
    gap B 2000-2500 before decode launch 1, split over three phases and
      more: host_sync 2000-2010 (nested in admission), admission 2010-2150,
      a one-op program busy 2100-2150 (under admission), nothing 2150-2200,
      prepare 2200-2300, launch 2300-2500
    decode module 2500-4000; host_sync ends 4020
    gap C 4000-4400: host_sync 4000-4020, account 4020-4030, publish
      4030-4130, poll 4130-4250, admission 4250-4260, prepare 4270-4300,
      launch 4300-4400 (4260-4270 lies in the step and in no phase)
    decode module 4400-5900"""
    dev = [
        module("decode_chunk_paged", 0, 900 * US), op(0, 900 * US),
        module("prefill_paged", 1000 * US, 1000 * US),
        op(1000 * US, 1000 * US),
        module("convert_element_type", 2100 * US, 50 * US),
        op(2100 * US, 50 * US),
        module("decode_chunk_paged", 2500 * US, 1500 * US),
        op(2500 * US, 700 * US), op(3200 * US, 800 * US),
        module("decode_chunk_paged", 4400 * US, 1500 * US),
        op(4400 * US, 1500 * US),
    ]
    hst = [
        host("admission", 900 * US, 2150 * US),
        launch(960 * US, 1000 * US, 0, "prefill", rows=1, units=4),
        host("host_sync", 1000 * US, 2010 * US),
        host("step", 2150 * US, 4200 * US),
        host("prepare", 2200 * US, 2300 * US),
        launch(2300 * US, 2500 * US, 1, "decode", rows=3),
        host("host_sync", 2500 * US, 4020 * US),
        host("account", 4020 * US, 4030 * US),
        host("publish", 4030 * US, 4130 * US),
        host("poll", 4130 * US, 4250 * US),
        host("admission", 4250 * US, 4260 * US),
        host("step", 4260 * US, 6100 * US),
        host("prepare", 4270 * US, 4300 * US),
        launch(4300 * US, 4400 * US, 2, "decode", rows=4),
        host("host_sync", 4400 * US, 5910 * US),
        # another thread's spans name nothing
        host("publish", 0, 6000 * US, line="/host:CPU#9"),
    ]
    return hst, dev


def ctx_of(hst, dev, **more):
    return {"trace": trace_reduce.Reduced(dev), "host_events": hst, **more}


def test_gap_split_over_phases_and_one_op_program(capsys):
    spans = host_spans.of(ctx_of(*hand_built()))
    assert spans.clock["launches"] == 3          # the cut launch counts not
    assert spans.clock["out_of_order"] == 0
    assert spans.clock["spread_us"] == pytest.approx(0.0)
    assert spans.clock["offset_ns"] == pytest.approx(5e9)
    assert spans.n_decode == 2
    # gap B is 500 us less the one-op program's 50, gap C 400
    assert spans.gap_ms() == pytest.approx((450 + 400) / 2 / 1e3)
    assert spans.gap_ms("host_sync") == pytest.approx((10 + 20) / 2 / 1e3)
    assert spans.gap_ms("publish") == pytest.approx(100 / 2 / 1e3)
    assert spans.gap_ms("poll") == pytest.approx(120 / 2 / 1e3)
    assert spans.gap_ms("prepare", "launch") == pytest.approx(
        (100 + 200 + 30 + 100) / 2 / 1e3)
    assert spans.gap_ns["admission"] == pytest.approx((90 + 10) * US)
    assert spans.gap_ns["account"] == pytest.approx(10 * US)
    assert spans.gap_ns[host_spans.NONE] == pytest.approx((50 + 10) * US)
    assert sum(spans.gap_ns.values()) == pytest.approx(850 * US)
    # all idle: gaps A, B, C; 60 us of them under no phase
    assert sum(spans.idle_ns.values()) == pytest.approx(950 * US)
    assert spans.attributed_share() == pytest.approx(1 - 60 / 950)
    assert spans.by_rows == {3: [1500 * US / 8], 4: [1500 * US / 8]}
    assert spans.step_ns == [2050 * US, 1840 * US]
    out = capsys.readouterr().out
    assert "clock: launches 3 out_of_order 0.0000" in out
    assert "idle_by_phase: poll seconds_in_span 0.000120" in out
    assert "launches: decode rows 4 count 1 device_ms_a_step 0.1875" in out
    # the one-op program began while the thread was in admission
    assert spans.others == {("jit_convert_element_type", "admission"):
                            [50 * US]}
    assert ("programs: jit_convert_element_type began_under admission "
            "count 1 device_s 0.000050") in out


def test_a_prefix_prefill_opens_the_gap_as_a_cold_one_does():
    """doc_qa: a hit's ``jit_prefill_prefix`` between two decode chunks;
    the gap before the second chunk is what lies behind that program."""
    hst, dev = hand_built()
    dev += [module("prefill_prefix", 4100 * US, 200 * US),
            op(4100 * US, 200 * US)]
    spans = host_spans.of(ctx_of(hst, dev))
    # gap C is 4300-4400 now, all of it under the decode's launch
    assert spans.gap_ms() == pytest.approx((450 + 100) / 2 / 1e3)
    assert spans.gap_ms("poll") == 0
    assert ("jit_prefill_prefix", "publish") not in spans.others
    assert sum(spans.idle_ns.values()) == pytest.approx((950 - 200) * US)


def test_nested_host_sync_wins_inside_admission():
    hst, dev = hand_built()
    pieces = host_spans.innermost(host_spans.serving_line(hst))
    at = {(a, b): name for a, b, name in pieces}
    assert at[(900 * US, 960 * US)] == "admission"
    assert at[(960 * US, 1000 * US)] == "launch"
    assert at[(1000 * US, 2010 * US)] == "host_sync"
    assert at[(2010 * US, 2150 * US)] == "admission"
    # no instant under two phases
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))


def test_a_module_before_its_annotation_fails_the_clock_check():
    hst, dev = hand_built()
    # the device's clock runs 250 us early: every module starts before
    # the annotation that launched it
    early = [Event(e.plane, e.line, e.name, e.start_ns - 250 * US, e.dur_ns)
             for e in dev]
    spans = host_spans.of(ctx_of(hst, early))
    assert spans.clock["out_of_order"] == 1.0
    # and a host_sync that ends before the module's last operation does
    hst = [host("host_sync", 2500 * US, 3900 * US)
           if (e.name, e.start_ns) == ("host_sync", 2500 * US) else e
           for e in hst]
    spans = host_spans.of(ctx_of(hst, dev))
    assert spans.clock["out_of_order"] == pytest.approx(1 / 3)
    # launches whose t_ns lie 80 us apart from their annotations
    hst = [launch(e.start_ns, e.end_ns, 1, "decode",
                  t_ns=e.start_ns - 5e9 - 80 * US)
           if e.args.get("launch") == 1 else e for e in hst]
    assert host_spans.of(ctx_of(hst, dev)).clock["spread_us"] == \
        pytest.approx(80.0)


def test_the_runtimes_events_lay_the_two_clocks_side_by_side(capsys):
    """The chip's clock 250 us early, as a trace gives it, and the
    runtime's own events round the two decode launches: handed to the
    chip's queue 10 us before the module's true start, noticed done 30
    and 20 us behind its true end."""
    hst, dev = hand_built()
    early = [Event(e.plane, e.line, e.name, e.start_ns - 250 * US, e.dur_ns)
             for e in dev]
    other = "/host:CPU#5"
    hst += [host("DoEnqueueProgram", 2490 * US, 2495 * US, line=other),
            host("ReadSyncFlag", 4030 * US, 4040 * US, line=other),
            host("tpu::System::Execute=>Done", 4045 * US, 4050 * US,
                 line=other),
            host("DoEnqueueProgram", 4390 * US, 4395 * US, line=other),
            host("ReadSyncFlag", 5920 * US, 5930 * US, line=other)]
    spans = host_spans.of(ctx_of(hst, early))
    # every launch allows 240-270 us; the second one 240-270, the first
    # 240-280: the middle of 240-270
    assert spans.clock["device_shift_us"] == pytest.approx(255.0)
    assert spans.clock["slack_us"] == pytest.approx(30.0)
    assert spans.clock["out_of_order"] == 0
    # the table is the sound clocks' to the 5 us the shift is off by
    assert spans.gap_ms() == pytest.approx(0.425)
    assert spans.gap_ms("poll") == pytest.approx(0.06)
    assert spans.gap_ms("prepare", "launch") == pytest.approx(0.215, abs=0.006)
    assert spans.gap_ms("host_sync") == pytest.approx(0.015, abs=0.006)
    assert "device_shift_us 255.0 slack_us 30.0" in capsys.readouterr().out
    # launches that contradict each other: a negative slack
    hst[-1] = host("ReadSyncFlag", 5600 * US, 5610 * US, line=other)
    assert host_spans.of(ctx_of(hst, early)).clock["slack_us"] < 0
    # none of the runtime's events: the trace's clocks as they are
    plain = host_spans.of(ctx_of(hand_built()[0], early))
    assert plain.clock["device_shift_us"] == 0
    assert plain.clock["slack_us"] is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_none(name):
    read = layer_metrics.load_reader(name)
    hst, dev = hand_built()
    assert read({}) is None
    assert read({"trace": trace_reduce.Reduced([])}) is None
    # a device trace of a program without annotations, and a CPU trace
    # (host events, no device plane)
    assert read(ctx_of([], dev)) is None
    assert read(ctx_of(hst, [])) is None


def test_readers_over_the_hand_built_list():
    hst, dev = hand_built()
    ctx = ctx_of(hst, dev, step_walls=[(0.0, 0.002), (1.0, 0.0019)])
    got = {name: layer_metrics.load_reader(name)(ctx) for name in READERS
           if name != "chunk_gap_p95_ms"}
    assert got["host_gap_ms"] == pytest.approx(0.425)
    assert got["host_gap_sync_ms"] == pytest.approx(0.015)
    assert got["host_gap_publish_ms"] == pytest.approx(0.05)
    assert got["host_gap_poll_ms"] == pytest.approx(0.06)
    assert got["host_gap_launch_ms"] == pytest.approx(0.215)
    assert got["idle_attributed"] == pytest.approx(100 * (1 - 60 / 950))
    assert got["engine_step_span_ms"] == pytest.approx(1.945)
    parts = sum(got[k] for k in ("host_gap_sync_ms", "host_gap_publish_ms",
                                 "host_gap_poll_ms", "host_gap_launch_ms"))
    assert parts <= got["host_gap_ms"]


def test_chunk_gap_is_the_tail_of_the_waits_between_stamps():
    read = layer_metrics.load_reader("chunk_gap_p95_ms")

    def record(first, chunks, retired, ok=True):
        return {"ok": ok, "events": [("queued", 0.0), ("first_token", first)]
                + [("decode_chunk", t) for t in chunks]
                + [("retired", retired)],
                "chunks": [(t, 8) for t in chunks]}
    steady = record(1.0, [1.1 + 0.1 * i for i in range(18)], 2.8)
    stalled = record(2.0, [2.1, 2.45, 2.55], 2.55)      # a prefill between
    late = record(3.0, [3.1, 9.0], 9.0)                 # past the window
    failed = record(1.0, [5.0], 5.0, ok=False)
    ctx = {"records": [steady, stalled, late, failed], "window": (0.0, 5.0)}
    # 21 gaps: nineteen of 0.1, one of 0.1 again, one of 0.35
    assert read(ctx) == pytest.approx(100.0)
    ctx["records"] = [stalled]
    assert read(ctx) == pytest.approx(350.0)
    assert read({"records": [late], "window": (0.0, 5.0)}) is None


SLICE = DATA / "trace_slice_host_v5e.json"


@pytest.mark.skipif(not SLICE.exists(), reason="no recorded slice")
def test_recorded_slice_of_chat():
    """One prefill and two decode launches of ``chat`` on the v5e with
    the host's annotations round them, as ``record_host_slice.py`` cut
    them from a traced run."""
    rec = json.loads(SLICE.read_text())
    dev = [Event(*e) for e in rec["device"]]
    hst = [HostEvent(*e) for e in rec["host"]]
    spans = host_spans.of(ctx_of(hst, dev))
    assert spans.clock["launches"] == 3
    assert spans.clock["out_of_order"] == 0
    assert spans.clock["spread_us"] < 50
    # that trace had the chip's clock 2.1 ms early
    assert spans.clock["device_shift_us"] == pytest.approx(2101.5, abs=0.1)
    assert 0 < spans.clock["slack_us"] < 500
    assert spans.n_decode == 2
    gap = spans.gap_ms()
    assert 1.0 < gap < 20.0
    named = sum(v for k, v in spans.gap_ns.items() if k != host_spans.NONE)
    assert named / sum(spans.gap_ns.values()) > 0.9
    assert spans.gap_ms("host_sync") + spans.gap_ms("publish") \
        + spans.gap_ms("poll") + spans.gap_ms("prepare", "launch") <= gap
    assert len(spans.step_ns) == 2
    assert rec["expect"]["host_gap_ms"] == pytest.approx(gap, rel=1e-6)

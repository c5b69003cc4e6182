"""Records a slice of the newest trace of a cell with the host's
annotations in it, for the tests of ``benchmark/lib/host_spans.py``
(benchmark/tests/data/trace_slice_host_v5e.json): one cold prefill and
the two decode launches behind it, from the start of the paged program
that ran before the prefill (its launch is cut, as by a trace's start)
to the end of the second decode step. Kept: the ``XLA Modules`` events
of the first chip as they are; its ``XLA Ops`` events as runs of busy
time (operations less than ``--merge-ns`` apart become one event: what
lies between two programs is kept to the nanosecond, a program's inner
pauses are not); the serving thread's ``engine.*`` events with their
arguments, and the runtime's own events that ``host_spans.device_shift``
lays the two clocks side by side with. Times start at 0. ``expect``
holds what the readers gave on the slice when it was cut.

    python3 benchmark/tests/record_host_slice.py \\
        --cell qwen2-7b-serve.chat --out chiprun_out/slice_host.json

Run on the machine that holds the trace (log/benchmark_trace/<cell>),
after a ``--trace 1`` run, in a process of its own: it reads a file and
needs no chip."""

import argparse
import glob
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import host_spans, launch_span, trace_reduce  # noqa: E402
from benchmark.lib.host_spans import HostEvent                  # noqa: E402
from benchmark.lib.trace_reduce import Event                    # noqa: E402


def busy_runs(ops, merge_ns):
    """``XLA Ops`` events as runs of busy time."""
    runs = []
    for e in sorted(ops, key=lambda e: e.start_ns):
        end = e.start_ns + e.dur_ns
        if runs and e.start_ns - runs[-1][1] < merge_ns:
            runs[-1][1] = max(runs[-1][1], end)
            runs[-1][2] += 1
        else:
            runs.append([e.start_ns, end, 1])
    return runs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--merge-ns", type=float, default=1000.0)
    args = ap.parse_args()
    paths = sorted(glob.glob(str(ROOT / "log" / "benchmark_trace" / args.cell
                                 / "**" / "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    device = trace_reduce.Reduced(trace_reduce.load(paths[-1]))
    plane = device.devices()[0]
    ops = sorted(device.of(trace_reduce.OPS_LINE, plane),
                 key=lambda e: e.start_ns)
    modules = sorted(device.of(trace_reduce.MODULES_LINE, plane),
                     key=lambda e: e.start_ns)
    host = host_spans.load(paths[-1])
    spans = host_spans.serving_line(host)
    matched = host_spans.match(spans, modules, ops)
    paged = [m for m in modules
             if trace_reduce.short_name(m.name) in launch_span.KINDS]
    # from the middle of the trace: a prefill and the two launches
    # behind it, both decode, with a paged program before the prefill
    triples = [t for t in zip(matched, matched[1:], matched[2:])
               if [a.args["kind"] for a, *_ in t]
               == ["prefill", "decode", "decode"]
               and [a.args["launch"] for a, *_ in t]
               == list(range(t[0][0].args["launch"],
                             t[0][0].args["launch"] + 3))
               and paged.index(t[0][1]) > 0]
    first, _, last = triples[len(triples) // 2]
    before = paged[paged.index(first[1]) - 1]
    lo = before.start_ns
    steps = [e for e in spans if e.name == host_spans.STEP
             and e.start_ns <= last[0].start_ns <= e.end_ns]
    hi = steps[-1].end_ns if steps else last[2].end_ns
    dev = [[plane, m.line, m.name, m.start_ns - lo, m.dur_ns]
           for m in modules if lo <= m.start_ns < hi]
    dev += [[plane, trace_reduce.OPS_LINE, f"busy({n} ops)", a - lo, b - a]
            for a, b, n in busy_runs((e for e in ops
                                      if lo <= e.start_ns < hi),
                                     args.merge_ns)]
    runtime = [e for e in host if e.name in host_spans.ENQUEUED
               or e.name in host_spans.DONE]
    hst = [[e.line, e.name, e.start_ns - lo, e.dur_ns, e.args]
           for e in spans + runtime if e.start_ns >= lo and e.end_ns <= hi]
    got = host_spans.analyse([HostEvent(*e) for e in hst],
                             [Event(*e) for e in dev])
    out = {"cell": args.cell, "merge_ns": args.merge_ns, "device": dev,
           "host": hst,
           "expect": {"host_gap_ms": got.gap_ms(), "clock": got.clock,
                      "gap_ns": got.gap_ns}}
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"record_host_slice: {len(dev)} device and {len(hst)} host "
          f"events of {args.cell} to {args.out}; host_gap_ms "
          f"{got.gap_ms():.4f} clock {got.clock}")


if __name__ == "__main__":
    main()

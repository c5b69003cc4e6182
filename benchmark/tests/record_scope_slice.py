"""Records a slice of the newest device trace of a cell whose readers
find their events by the engine's table of named scopes
(``benchmark/lib/dsa_span.py``), for the tests of those readers
(benchmark/tests/data): one whole decode launch, the first ``--blocks``
blocks of one cold prefill that began inside the trace, and the tail of
the launch the trace's start cut, each kept to the events the table
knows, the grouped expert products and the loops round them; names cut
to 200 characters, times from 0. Beside them the table restricted to the
instructions kept, the launches' entries near the span and the span.

    DSA_SPAN_RECORD=chiprun_out/dsa_span.json python3 benchmark/run.py \\
        --workload glm-5-serve.long_ctx --seed 1 --seconds 51 --trace 1
    python3 benchmark/tests/record_scope_slice.py \\
        --cell glm-5-serve.long_ctx --handed chiprun_out/dsa_span.json \\
        --out chiprun_out/trace_slice_glm_v5e.json

Run on the machine that holds the trace, in a process of its own."""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import dsa_span, launch_span, trace_reduce  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--handed", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--blocks", type=int, default=3)
    ap.add_argument("--tail-events", type=int, default=400)
    args = ap.parse_args()
    with open(args.handed) as f:
        handed = json.load(f)
    reduced = trace_reduce.reduce_dir(ROOT / "log" / "benchmark_trace"
                                      / args.cell)
    plane = reduced.devices()[0]
    ops = sorted(reduced.of(trace_reduce.OPS_LINE, plane),
                 key=lambda e: e.start_ns)
    modules = sorted(reduced.of(trace_reduce.MODULES_LINE, plane),
                     key=lambda e: e.start_ns)
    known = {n for t in handed["scopes"].values() for n in t}

    def keep(e):
        m = dsa_span.INSTRUCTION.match(e.name)
        return (m and m.group(1) in known) \
            or dsa_span.EXPERT_PRODUCT.match(e.name) \
            or trace_reduce.label(e.name).split(" ")[0] \
            in trace_reduce.CONTAINERS

    events, at = [], 0.0

    def lay(found, lo, module=None):
        nonlocal at
        if module is not None:
            events.append([plane, module.line, module.name, at,
                           max(e.start_ns + e.dur_ns for e in found) - lo])
        events.extend([plane, e.line, e.name[:200], at + e.start_ns - lo,
                       e.dur_ns] for e in found)
        at += max(e.start_ns + e.dur_ns for e in found) - lo + 1e6

    first = modules[0].start_ns
    tail = [e for e in ops if e.start_ns < first and keep(e)]
    if tail:
        tail = tail[-args.tail_events:]
        lay(tail, tail[0].start_ns)
    for program, cut in (("jit_decode_chunk_paged", None),
                         ("jit_prefill_paged", args.blocks)):
        launches = [m for m in modules
                    if trace_reduce.short_name(m.name) == program]
        if not launches:
            continue
        m = launches[len(launches) // 2]
        own = [e for e in ops if m.start_ns <= e.start_ns
               < m.start_ns + m.dur_ns and keep(e)]
        if cut is not None:     # the launch's first blocks, as a trace's
            products, n = 0, len(own)       # end would have cut it
            for i, e in enumerate(own):
                products += bool(dsa_span.EXPERT_PRODUCT.match(e.name))
                if products == cut * 15:
                    n = i + 1
                    break
            own = own[:n]
        lay(own, m.start_ns, m)
    kept = {dsa_span.INSTRUCTION.match(e[2]).group(1) for e in events
            if dsa_span.INSTRUCTION.match(e[2])}
    out = {"events": events,
           "scopes": {p: {n: s for n, s in t.items() if n in kept}
                      for p, t in handed["scopes"].items()},
           "trace_span": handed["trace_span"],
           "launches": [e for e in handed["launches"]
                        if handed["trace_span"][0] - 60 <= e[0]
                        < handed["trace_span"][1]],
           "kinds": launch_span.KINDS}
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"record_scope_slice: {len(events)} events to {args.out}")


if __name__ == "__main__":
    main()

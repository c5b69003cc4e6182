"""Records a slice of the newest device trace of a cell, for the tests of
the readers (benchmark/tests/data): the events of one launch of each
program named, on the ``XLA Modules`` line and, of the ``XLA Ops`` line,
those whose name matches ``--keep`` (the kernels a reader looks for and
the loops round them), names cut to 200 characters, the launches laid
one behind the other from time 0.

    python3 benchmark/tests/record_slice.py --cell mimo-v2.5-serve.long_in \\
        --programs jit_decode_chunk_paged jit_prefill_paged \\
        --keep 'ragged-dot|paged_decode_qk|while' --out chiprun_out/slice.json

Run on the machine that holds the trace (log/benchmark_trace/<cell>),
after a ``--trace 1`` run, in a process of its own: it reads a file and
needs no chip."""

import argparse
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import trace_reduce  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--programs", nargs="+", required=True)
    ap.add_argument("--keep", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    reduced = trace_reduce.reduce_dir(ROOT / "log" / "benchmark_trace"
                                      / args.cell)
    plane = reduced.devices()[0]
    keep = re.compile(args.keep)
    modules = sorted(reduced.of(trace_reduce.MODULES_LINE, plane),
                     key=lambda e: e.start_ns)
    out, at = [], 0.0
    for program in args.programs:
        launches = [m for m in modules
                    if trace_reduce.short_name(m.name) == program]
        if not launches:
            continue
        # a launch from the middle of the trace: whole, and warm
        m = launches[len(launches) // 2]
        lo, hi = m.start_ns, m.start_ns + m.dur_ns
        out.append([plane, m.line, m.name, at, m.dur_ns, program])
        out += [[plane, e.line, e.name[:200], at + e.start_ns - lo, e.dur_ns,
                 program]
                for e in reduced.of(trace_reduce.OPS_LINE, plane)
                if lo <= e.start_ns < hi and keep.search(e.name)]
        at += m.dur_ns + 1e6
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"record_slice: {len(out)} events of {args.programs} "
          f"to {args.out}")


if __name__ == "__main__":
    main()

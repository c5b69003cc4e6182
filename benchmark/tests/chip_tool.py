"""Runs on the chip that are not the benchmark's own: the one-time rate
sweep and the control of ``correct``. Same runner code, with one value
of the traffic file overridden from the command line.

    python3 benchmark/tests/chip_tool.py --workload qwen2-7b-serve.chat \\
        --seed 7 --seconds 30 --trace 0 [--rate 3.0] [--control 1] [--set k=v ...]

``--manifest benchmark/tests/open_cells.json`` runs a cell that is not in
BENCHMARK.json yet (PERF.md, Open questions): its entries as a later PR
would add them. ``--rate`` overrides ``arrivals.rate_per_s`` (the sweep); ``--control 1``
also computes the int8 control's numbers over the same sample and prints
them as ``check: control.*`` lines; ``--set a.b=json`` overrides any
other value of the mix. The engine's own lower precision is read with
``--set engine.kv_dtype='"int8"' --set engine.block_size=32`` (an int8
page is one tile of 32 tokens).
"""

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmark import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=float)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--cpu", type=int, default=0)
    ap.add_argument("--manifest")
    args, rest = ap.parse_known_args()
    over = {}
    if args.rate is not None:
        over = {"arrivals": {"rate_per_s": args.rate}}
    for item in args.set:
        path, value = item.split("=", 1)
        node = over
        keys = path.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = json.loads(value)
    manifest = HERE / "rehearsal" / "manifest.json" if args.cpu else None
    if args.manifest:
        manifest = pathlib.Path(args.manifest).resolve()
    run.main(rest, allow_cpu=bool(args.cpu), manifest_path=manifest,
             mix_override=over, control=bool(args.control))


if __name__ == "__main__":
    main()

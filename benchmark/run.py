"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell by name, reads its configuration's and its traffic mix's
files, hands them to the runner the mix names, and prints the result as
the last line of standard output. Without a TPU (or with fewer chips
than the cell asks for) it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import pathlib      # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def merge(base, over):
    """``over`` laid over ``base``, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def main(argv=None, allow_cpu=False, manifest_path=None, mix_override=None,
         control=False):
    """``allow_cpu``, ``manifest_path``, ``mix_override`` and ``control``
    are for the tools under benchmark/tests (rehearsal, rate sweep,
    control); the benchmark's command passes none of them."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.lib import device, manifest as mf, program
    manifest = mf.load_manifest(manifest_path)
    cell = mf.find_cell(manifest, args.workload)
    cfg, mix = mf.cell_files(manifest, cell)
    if mix_override:
        mix = merge(mix, mix_override)
    program.enable_compile_cache()
    t_imports = time.perf_counter()
    devices = device.require_chips(cell["chips"], allow_cpu=allow_cpu)
    t_chip = time.perf_counter()
    # traces are written inside the checkout, under a name git ignores
    trace_dir = ROOT / "log" / "benchmark_trace" / cell["name"]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    runner = importlib.import_module(f"benchmark.runners.{mix['runner']}")
    result = runner.run({
        "cell": cell, "cfg": cfg, "mix": mix, "manifest": manifest,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "devices": devices, "t_process": T_PROCESS,
        "t_imports": t_imports, "t_chip": t_chip,
        "trace_dir": trace_dir,
        "control": control})
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()

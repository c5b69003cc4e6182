"""Two sets of runs of one cell, read as the driver's check reads them.

    python3 benchmark/tests/spread_table.py <dir> [--cell <name>] [--markdown 1]

``<dir>`` holds the standard output of runs of ``benchmark/run.py``, one
file a run, named ``<cell>__<side>__<i>__<seed>.out`` (side ``A`` or
``B``: two copies of one tree, run in pairs). For each end-to-end metric
of BENCHMARK.json, and each statistic of the runs' ``ladder:`` lines, it
prints every run, each side's median and three spreads of the side: the
IQR share of all its runs (``stats.iqr_share``), the IQR share with the
run farthest from the median left out (what the driver reads for
tightness) and the range of those over the median (ISSUE 35's stricter
reading). A bound holds the rule of PERF.md, section 2, if it is at
least twice the mean of the sides' IQR shares without the farthest run,
at most eight times the widest IQR share of all runs (1% is never too
loose), and the sides' medians lie within it of each other. It also
gathers the runs' ``setup:`` lines by phase. It measures nothing itself
and needs no chip.
"""

import argparse
import collections
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmark.lib import manifest as mf  # noqa: E402
from benchmark.lib import stats  # noqa: E402


def read_run(path):
    """(result object, {phase: seconds} of the ``setup:`` line). The
    statistics of the ``ladder:`` lines join the result's metrics, so
    that their spreads are read beside the end-to-end metrics'."""
    lines = path.read_text().splitlines()
    result = json.loads(lines[-1])
    phases = {}
    for line in lines:
        if not line.startswith(("setup: ", "ladder: ")):
            continue
        words = line.split()[1:]
        pairs = {k: float(v) for k, v in zip(words[::2], words[1::2])}
        if line.startswith("setup: "):
            phases = pairs
        else:
            for k, v in pairs.items():
                result["metrics"].setdefault(k, {"value": v})
    return result, phases


def table(runs, bounds, markdown=False):
    """``runs``: {side: [(i, seed, result, phases)]}. Prints one cell."""
    ok = True
    for side, rows in sorted(runs.items()):
        bad = [seed for _, seed, r, _ in rows if not r["correct"]]
        print(f"  side {side}: seeds {[seed for _, seed, _, _ in rows]} "
              f"not correct: {bad or 'none'}")
        ok &= not bad
    for name, bound in bounds.items():
        medians, iqr_all, iqr_kept, range_kept, shown = {}, {}, {}, {}, []
        for side, rows in sorted(runs.items()):
            vals = [r["metrics"][name]["value"] for _, _, r, _ in rows
                    if name in r["metrics"]]
            if len(vals) < 3:
                continue
            kept = stats.without_farthest(vals)
            medians[side] = stats.median(vals)
            iqr_all[side] = stats.iqr_share(vals)
            iqr_kept[side] = stats.iqr_share(kept)
            range_kept[side] = stats.range_share(kept)
            runs_txt = " ".join(f"{v:.2f}" for v in vals)
            shown.append(f"{runs_txt}; **{medians[side]:.2f}**; "
                         f"{100 * iqr_all[side]:.2f} / {100 * iqr_kept[side]:.2f} / "
                         f"{100 * range_kept[side]:.2f}")
            if not markdown:
                print(f"  {name} {side}: {runs_txt} | median {medians[side]:.4f} "
                      f"iqr {iqr_all[side]:.4f} iqr_without_farthest "
                      f"{iqr_kept[side]:.4f} range_without_farthest "
                      f"{range_kept[side]:.4f}")
        if not medians:
            continue
        tight = 2 * sum(iqr_kept.values()) / len(iqr_kept)
        loose = max(0.01, 8 * max(iqr_all.values()))
        sides = sorted(medians)
        diff = abs(medians[sides[-1]] - medians[sides[0]]) / medians[sides[0]]
        if markdown:
            print(f"| `{name}` | " + " | ".join(shown)
                  + f" | {100 * diff:.2f} | {100 * tight:.2f} - {100 * loose:.1f} "
                  f"| {100 * 2 * max(range_kept.values()):.2f} |")
            continue
        line = (f"  {name}: bound {bound} may lie in {tight:.4f} - {loose:.4f}, "
                f"medians differ {diff:.4f}, ISSUE 35 asks for "
                f"{2 * max(range_kept.values()):.4f}")
        if bound is None:           # a statistic of the ladder
            print(line)
            continue
        holds = tight <= bound <= loose and diff < bound
        print(line + (" HOLDS" if holds else " DOES NOT HOLD"))
        ok &= holds or name == "setup_s"    # set-up is judged by its median alone
    phases = collections.defaultdict(list)
    for rows in runs.values():
        for _, _, _, ph in rows:
            for k, v in ph.items():
                phases[k].append(v)
    for k, vals in phases.items():
        print(f"  setup phase {k}: min {min(vals):.3f} median "
              f"{stats.median(vals):.3f} max {max(vals):.3f}")
    return ok


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dir")
    ap.add_argument("--cell")
    ap.add_argument("--markdown", type=int, default=0,
                    help="1: one table row a metric, as PERF.md prints them")
    args = ap.parse_args()
    manifest = mf.load_manifest()
    cells = collections.defaultdict(lambda: collections.defaultdict(list))
    for path in sorted(pathlib.Path(args.dir).glob("*__*__*__*.out")):
        cell, side, i, seed = path.stem.split("__")
        try:
            result, phases = read_run(path)
        except (ValueError, IndexError):
            print(f"no result line in {path}")
            continue
        cells[cell][side].append((int(i), int(seed), result, phases))
    ok = True
    for cell, runs in sorted(cells.items()):
        if args.cell and cell != args.cell:
            continue
        print(cell)
        bounds = {e["name"]: e["bound"] for e in manifest["end_to_end"]
                  if mf.metric_reports_in(e, cell, manifest)}
        for rows in runs.values():          # the ladder, with no bound
            for _, _, result, _ in rows:
                for extra in result["metrics"]:
                    bounds.setdefault(extra, None)
        for rows in runs.values():
            rows.sort()
        ok &= table(runs, bounds, bool(args.markdown))
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()

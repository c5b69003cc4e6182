"""Reads BENCHMARK.json and the data files it names. A cell is found by
its name; what belongs to a configuration or a traffic mix is read from
that one's own file, never from a table in code."""

from __future__ import annotations

import importlib
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_manifest(path=None):
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def traffic_path(traffic, manifest=None):
    """``benchmark/traffic/<mix>.json``; the rehearsal's manifest (under
    benchmark/tests) names a directory of its own."""
    folder = (manifest or {}).get("traffic_dir", "benchmark/traffic")
    return ROOT / folder / f"{traffic}.json"


def find_cell(manifest, name):
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no cell named {name!r} in BENCHMARK.json")


def cell_files(manifest, cell):
    """(configuration dict, traffic dict) of one cell."""
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    return (load_json(ROOT / cfg_entry["file"]),
            load_json(traffic_path(cell["traffic"], manifest)))


def serve_modules(cfg):
    """(builder, reference) of a serve configuration: the modules under
    benchmark/lib that its file names as ``program.build`` (default
    ``program``; gives ``build_model(cfg, seed)`` and
    ``kv_bytes_per_block(cfg, block_size)``) and ``program.reference``
    (default ``reference``; gives ``served_gaps(seed, cfg, seq, n_prompt,
    control)``). A later configuration brings modules of its own."""
    names = cfg.get("program", {})
    return tuple(
        importlib.import_module(f"benchmark.lib.{names.get(key, default)}")
        for key, default in (("build", "program"), ("reference", "reference")))


def metric_reports_in(metric, cell_name, manifest):
    """Whether ``metric`` (an entry of end_to_end or per_layer) is due in
    the cell: its own ``workloads`` list if it has one, else every cell
    that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    target = next(m for m in manifest["end_to_end"] if m["name"] == moves)
    return metric_reports_in(target, cell_name, manifest)

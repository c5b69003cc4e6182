"""Finds the per-layer readers by name. A metric ``x`` of BENCHMARK.json
is read by ``benchmark/layer_metrics/x.py``, a file with one function
``read(ctx)`` that returns the value or ``None`` when it found nothing
to read; such a metric is left out of the line."""

from __future__ import annotations

import importlib.util

from .manifest import BENCH_DIR, metric_reports_in


def load_reader(name):
    path = BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_all(manifest, cell_name, ctx):
    out = {}
    for metric in manifest["per_layer"]:
        if not metric_reports_in(metric, cell_name, manifest):
            continue
        value = load_reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out

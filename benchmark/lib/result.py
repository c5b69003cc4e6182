"""What the runners share at both ends of a run: the thread that
profiles part of the window, and the result line."""

from __future__ import annotations

import sys
import threading
import time

from . import device as dev
from . import layer_metrics, trace_reduce


class TraceWindow(threading.Thread):
    """Profiles part of the window (``"trace"`` in the mix's file: its
    start and length in seconds) from a thread of its own, so that load
    is offered, or steps are fed, on schedule."""

    def __init__(self, ctx, t0, default_seconds):
        super().__init__(daemon=True)
        spec = ctx["mix"].get("trace", {})
        seconds = float(ctx["seconds"])
        self.start_at = t0 + spec.get("start_s", seconds / 3)
        self.seconds = min(spec.get("seconds", default_seconds), seconds / 2)
        self.out_dir = str(ctx["trace_dir"])
        self.span = None            # on the host's perf_counter

    def run(self):
        import jax
        time.sleep(max(0.0, self.start_at - time.perf_counter()))
        jax.profiler.start_trace(self.out_dir)
        a = time.perf_counter()
        time.sleep(self.seconds)
        b = time.perf_counter()
        jax.profiler.stop_trace()
        self.span = (a, b)


def start_trace(ctx, t0, default_seconds=3.0):
    if not ctx["trace"]:
        return None
    tracer = TraceWindow(ctx, t0, default_seconds)
    tracer.start()
    return tracer


def check_line(name, value, limit):
    return f"check: {name} {value:.6g} limit {limit:.6g}"


def print_checks(checks):
    """Each number compared beside its limit; ``control.*`` lines are
    the control's readings and decide nothing."""
    for check in checks:
        print(check_line(*check))
    return all(v <= lim for name, v, lim in checks
               if not name.startswith("control."))


def assemble(ctx, correct, attempted, failed, peak, e2e, reader_ctx, checks=()):
    """The result object: end-to-end metrics, or with ``--trace 1`` the
    per-layer metrics, the device's busy time and the breakdown; last,
    each number ``correct`` compared beside its limit, which are also
    the run's last lines on standard error."""
    devices = ctx["devices"]
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed,
              "device": dev.describe(devices, memory_peak_bytes=peak)}
    if ctx["trace"]:
        reduced = trace_reduce.reduce_dir(ctx["trace_dir"])
        reader_ctx = {
            **reader_ctx, "cfg": ctx["cfg"], "mix": ctx["mix"],
            "trace": reduced, "n_devices": len(devices),
            "e2e": {k: v for k, (v, _) in e2e.items()},
            "peaks": dev.peaks_for(devices[0].device_kind)
            if devices[0].platform == "tpu" else None}
        result["metrics"] = layer_metrics.read_all(
            ctx["manifest"], ctx["cell"]["name"], reader_ctx)
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in e2e.items()}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    for check in checks:
        print(check_line(*check), file=sys.stderr)
    sys.stderr.flush()
    return result

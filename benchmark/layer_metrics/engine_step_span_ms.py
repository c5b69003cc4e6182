"""Engine step: median length of the traced span's ``engine.step``
annotations (``StepProfiler.begin_step`` / ``end_step`` round one
``decode_once``) that hold a decode launch: ``engine_step_ms`` from
inside the program, printed beside the harness's wrapper's readings."""

from benchmark.lib import host_spans, stats


def read(ctx):
    spans = host_spans.of(ctx)
    if spans is None or not spans.step_ns:
        return None
    value = stats.median(spans.step_ns) / 1e6
    # the wrapper's walls over the window, and over the traced span alone
    # (the window is no steady state: only the second compares)
    lo, hi = ctx.get("trace_span") or (float("-inf"), float("inf"))
    walls = [(t, 1e3 * w) for t, w in ctx.get("step_walls") or []
             if w > 2e-4]
    in_span = [w for t, w in walls if lo <= t < hi]
    print(f"engine_step_span_ms: spans {len(spans.step_ns)} median "
          f"{value:.4f} engine_step_ms "
          f"{stats.median([w for _, w in walls]) if walls else float('nan'):.4f}"
          f" in_the_span "
          f"{stats.median(in_span) if in_span else float('nan'):.4f}")
    return value

"""Engine step: median wall time of one ``DecodeEngine.decode_once`` in
the window (a decode chunk of ``chunk`` tokens for every live row, with
its launch, its blocking read-back and the host's bookkeeping), from
the wrapper the harness puts round the bound method. Calls on an idle
engine return at once and are left out."""

from benchmark.lib import stats


def read(ctx):
    if not ctx.get("step_walls"):
        return None
    t0, t1 = ctx["window"]
    walls = [1e3 * w for t, w in ctx["step_walls"]
             if t0 <= t <= t1 and w > 2e-4]
    return stats.median(walls) if walls else None

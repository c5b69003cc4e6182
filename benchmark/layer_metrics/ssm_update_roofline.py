"""Kernels: the decode state update's share of its byte roofline in the
traced part of the window. The trace names the Pallas kernel by its
``name=`` (``ssm_decode_update``: events ``%ssm_decode_update.N = ...
custom-call(`` on the chip's ``XLA Ops`` line, compiled for the v5e and
seen there); each event is one Mamba layer of one decode step, for that
step's live rows. Bytes: what ``benchmark/kernels/ssm_update.py`` says
one (row, layer, step) has to move, times the events, times the live
rows a step carried (``engine_ssm_row_steps_total`` over the decode
steps, across the window: the load is stationary, the trace covers a
part of it), over the HBM peak; over the events' device time. Without the
kernel in the trace or the counter in ``stats()`` there is nothing to
read."""

import re

from benchmark.kernels import ssm_update
from benchmark.lib import trace_reduce

EVENT = re.compile(r"^%?ssm_decode_update[.\d]* = ")


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    before, after = ctx.get("before"), ctx.get("after")
    cfg = ctx.get("cfg") or {}
    if trace is None or not peaks or not before or not after \
            or "ssm_row_steps" not in after or "mamba_n_heads" not in cfg:
        return None
    steps = after["device_steps"] - before["device_steps"]
    planes = trace.devices()
    if steps <= 0 or not planes:
        return None
    events = [e for e in trace.of(trace_reduce.OPS_LINE, planes[0])
              if EVENT.match(e.name)]
    seconds = sum(e.dur_ns for e in events) / 1e9
    if not seconds:
        return None
    rows = (after["ssm_row_steps"] - before.get("ssm_row_steps", 0)) / steps
    least = ssm_update.least_seconds(
        rows * len(events), cfg["mamba_n_heads"], cfg["mamba_d_head"],
        cfg["mamba_d_state"], peaks)
    return 100.0 * least / seconds

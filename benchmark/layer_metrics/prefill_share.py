"""Engine step: device time of the prefill programs over the device's
busy time, in the traced part of the window. The trace names a launched
program ``jit_<function>(<id>)`` on the chip's ``XLA Modules`` line; the
engine's prefill functions are ``prefill_paged`` (cold: since PR 33
the prompt's blocks of 256 rows, not the ``s_max`` window) and
``prefill_prefix`` (the tail after a cache hit),
so the programs are ``jit_prefill_paged`` and ``jit_prefill_prefix``
(seen on the v5e, PR 23). A rename in the program makes this reader
return nothing, and the metric is then left out, not guessed."""

from benchmark.lib import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.busy_s:
        return None
    mods = trace.seconds_by(trace_reduce.MODULES_LINE)
    if not any(name.startswith("jit_decode_chunk") for name in mods):
        return None         # the names this reader knows are not there
    prefill = sum(s for name, s in mods.items()
                  if name.startswith("jit_prefill"))
    return 100.0 * prefill / trace.busy_s

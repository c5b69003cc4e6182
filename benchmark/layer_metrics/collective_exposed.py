"""Collectives: share of the traced span in which a collective ran on
the first chip's operation stream and no other operation did. The
``XLA Ops`` line names operations after their HLO: ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all``, and the ``-start`` / ``-done`` halves of their
asynchronous forms (a ``-done`` is the wait for bytes still in flight)."""

import re

COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\(")


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window_s:
        return None
    if ctx.get("n_devices", 1) < 2:
        return None
    exposed = trace.exposed_seconds(lambda name: bool(COLLECTIVE.search(name)))
    return 100.0 * exposed / trace.window_s

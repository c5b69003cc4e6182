"""Device: share of the traced part of the window in which no operation
ran on the chip: 1 - union of the device-op intervals over the span
from the first traced operation to the last."""


def read(ctx):
    idle = ctx["trace"].idle_share() if ctx.get("trace") else None
    return None if idle is None else 100.0 * idle

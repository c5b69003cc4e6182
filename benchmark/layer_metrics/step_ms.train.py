"""Train step: median host time of one step of the window, each closed
with ``block_until_ready`` (so a step's time includes its launch, its
input transfer and the wait for the device)."""

from benchmark.lib import stats


def read(ctx):
    walls = ctx.get("step_walls_train")
    return 1e3 * stats.median(walls) if walls else None

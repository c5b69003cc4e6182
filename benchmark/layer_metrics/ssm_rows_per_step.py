"""Engine step: live rows a decode step carries, for a model whose slots
hold a recurrent state: ``engine_ssm_row_steps_total`` (live rows summed
over decode steps) over ``engine_device_steps_total`` (the decode steps),
both from the engine's ``stats()`` before and after the window. What the
state update costs follows this number, not the slots. An engine without
the counter (any model with no per-slot state, or a program older than
the counter) gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "ssm_row_steps" not in after:
        return None
    steps = after["device_steps"] - before["device_steps"]
    if steps <= 0:
        return None
    return (after["ssm_row_steps"] - before.get("ssm_row_steps", 0)) / steps

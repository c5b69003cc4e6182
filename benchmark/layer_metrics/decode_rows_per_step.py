"""Engine step: rows a decode step of the paged engine carries, whatever
the model: ``engine_decode_row_steps_total`` (rows summed over the steps
of its decode chunks) over ``engine_device_steps_total``, from the
engine's ``stats()`` before and after the window. Above the knee the
tokens per second are this number over the step's time. A program without
the counter gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "decode_row_steps" not in after:
        return None
    steps = after["device_steps"] - before["device_steps"]
    if steps <= 0:
        return None
    return (after["decode_row_steps"]
            - before.get("decode_row_steps", 0)) / steps

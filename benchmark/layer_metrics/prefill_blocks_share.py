"""Engine step: the blocks of rows the cold prefills of the window ran
(``engine_prefill_blocks_total``) as a share of the blocks their
``s_max`` windows hold (``engine_prefill_window_blocks_total``), from
the engine's ``stats()`` before and after the window. A cold prefill
that walks only the blocks its prompt fills reads the prompts' share of
the window; one that runs the whole window reads 100. An engine without
the counters, or a window with no cold prefill, gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "prefill_window_blocks" not in after:
        return None
    window = after["prefill_window_blocks"] - before["prefill_window_blocks"]
    if window <= 0:
        return None
    return 100.0 * (after["prefill_blocks"] - before["prefill_blocks"]) / window

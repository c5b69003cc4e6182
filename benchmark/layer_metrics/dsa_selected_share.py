"""Engine step: of the cached tokens the decode steps' indexer scored,
the share the latent attention then read:
``engine_dsa_selected_tokens_total`` over
``engine_dsa_scored_tokens_total`` (a live row's ``min(context,
index_topk)`` and its context, a layer and step, counted by the engine at
every decode launch), from ``stats()`` before and after the window. 100
under ``index_topk``; the longer the contexts, the smaller. An engine
without the counters gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "dsa_scored_tokens" not in after:
        return None
    scored = after["dsa_scored_tokens"] - before.get("dsa_scored_tokens", 0)
    selected = after["dsa_selected_tokens"] \
        - before.get("dsa_selected_tokens", 0)
    if scored <= 0:
        return None
    return 100.0 * selected / scored

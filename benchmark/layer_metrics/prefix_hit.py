"""KV pages and prefix cache: prompt tokens served from the cache
(``engine_prefix_hit_tokens_total``, the difference over the window) as
a share of the prompt tokens of the requests admitted in it."""


def read(ctx):
    if "after" not in ctx:
        return None
    t_close = ctx["window"][1]
    admitted = sum(r["n_prompt"] for r in ctx["records"]
                   if any(s == "admitted" and t <= t_close
                          for s, t in r["events"]))
    if not admitted:
        return None
    hit = ctx["after"]["prefix_hit_tokens"] - ctx["before"]["prefix_hit_tokens"]
    return 100.0 * hit / admitted

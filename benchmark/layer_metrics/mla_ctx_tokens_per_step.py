"""Engine step: the cached latents a decode step's attention read:
``engine_mla_ctx_tokens_total`` (a live row's context a layer and step,
counted by the engine at every decode launch) over
``engine_device_steps_total``, from ``stats()`` before and after the
window: how loaded the decode kernel was, so that two readings of its
roofline share can be compared. The line beside it says how the router's
groups spread a step's rows: held experts and groups visited a layer and
step or block (``engine_moe_expert_visits_total``,
``engine_moe_groups_visited_total`` over ``engine_moe_pairs_total``'s
layer-units). An engine without the counter gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "mla_ctx_tokens" not in after:
        return None
    grown = lambda name: after.get(name, 0) - before.get(name, 0)
    steps = grown("device_steps")
    if steps <= 0:
        return None
    cfg = ctx.get("cfg") or {}
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    units = moe_layers * (steps + grown("prefill_blocks"))
    print(f"moe: pairs {grown('moe_pairs')} expert_visits "
          f"{grown('moe_expert_visits')} groups_visited "
          f"{grown('moe_groups_visited')} layer_units {units} "
          f"held_experts_a_unit {grown('moe_expert_visits') / units:.2f} "
          f"groups_a_unit {grown('moe_groups_visited') / units:.2f} "
          f"row_steps {grown('decode_row_steps')}")
    return grown("mla_ctx_tokens") / steps

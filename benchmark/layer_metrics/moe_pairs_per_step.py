"""Engine step: (token, held expert) pairs the expert layers computed a
device step: ``engine_moe_pairs_total`` (counted on the device by both
paged programs, all expert layers) over ``engine_device_steps_total``,
from the engine's ``stats()`` before and after the window. What the
expert products cost follows this number and the experts it visits,
not the slots. Printed beside it: the visits, and what a router that
spreads its choices evenly would bring one expert layer at the decode
steps' rows (``rows * top_k * held / experts`` pairs, ``held * (1 - (1 -
top_k / experts) ** rows)`` held experts visited). An engine without
the counter (a model with no held experts, or a program older than the
counter) gives nothing to read."""


def read(ctx):
    before, after = ctx.get("before"), ctx.get("after")
    if not before or not after or "moe_pairs" not in after:
        return None
    steps = after["device_steps"] - before["device_steps"]
    if steps <= 0:
        return None
    pairs = after["moe_pairs"] - before.get("moe_pairs", 0)
    visits = after["moe_expert_visits"] - before.get("moe_expert_visits", 0)
    cfg = ctx.get("cfg") or {}
    if sum(cfg.get("moe_layer_freq") or [0]) and "decode_row_steps" in after:
        rows = (after["decode_row_steps"]
                - before.get("decode_row_steps", 0)) / steps
        held = cfg["n_routed_experts"]
        experts = held * (cfg.get("expert_share") or {"of": 1})["of"]
        k = cfg["num_experts_per_tok"]
        print(f"moe: pairs {pairs} expert_visits {visits} "
              f"pairs_per_visit {pairs / max(visits, 1):.3f} "
              f"decode_rows_per_step {rows:.2f} an even router at those "
              f"rows: pairs_per_layer_step {rows * k * held / experts:.2f} "
              f"visits_per_layer_step "
              f"{held * (1.0 - (1.0 - k / experts) ** rows):.2f} "
              f"of {held} held")
    return pairs / steps

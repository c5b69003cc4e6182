"""Train step: model FLOP/s utilization. Operations the forward and
backward passes need per token (benchmark/kernels/train_step.py: 6 per
matmul parameter plus causal attention; recomputed operations do not
count) times ``train_tok_s`` over chips times the chip's bf16 peak."""

from benchmark.kernels import train_step


def read(ctx):
    if ctx.get("peaks") is None or "train_tok_s" not in ctx.get("e2e", {}):
        return None
    seq = ctx["mix"]["seq"]
    per_token = train_step.flops_per_token(ctx["cfg"], seq)
    achieved = per_token * ctx["e2e"]["train_tok_s"]
    return 100.0 * achieved / (ctx["n_devices"] * ctx["peaks"]["bf16_flops_per_s"])

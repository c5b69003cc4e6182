"""The cold prefill of dense attention over a latent cache
(paddle_tpu/models/deepseek_v3.py, ``_prefill_attend``): for a prompt of
``n`` tokens and each layer, every query reads every earlier token.

Needs, a layer: ``n (n + 1) / 2`` causal pairs at ``2 * heads *
((qk_nope_head_dim + qk_rope_head_dim) + v_head_dim)`` operations each,
81.9 k at these widths: the EXPANDED form's count (keys and values
up-projected from the latents), the lesser of the two forms. The absorbed
form does ``2 * heads * ((kv_lora_rank + qk_rope_head_dim) +
kv_lora_rank)`` = 278.5 k a pair and saves the expansion: a program in it
reads 29% at the matrix unit's peak, never over 100. Bound: operations,
over the chip's bfloat16 peak; the latents a block reads are reused by
its 256 queries. The counts state the work, whatever implements it.
"""


def pairs(n):
    """Causal pairs of one layer over the first ``n`` tokens of a prompt
    (``n`` may be a fraction: a launch cut by a trace)."""
    return n * (n + 1) / 2.0


def operations(causal_pairs, cfg):
    return 2.0 * causal_pairs * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def least_seconds(causal_pairs, cfg, peaks):
    return operations(causal_pairs, cfg) / peaks["bf16_flops_per_s"]

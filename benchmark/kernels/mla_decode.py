"""The decode step of dense attention over a latent cache
(paddle_tpu/models/deepseek_v3.py, ``_decode_attention``; on the chip
``paddle_tpu/kernels/latent_attention.py``): for one query token per live
row, every head's absorbed query reads EVERY cached latent of the row,
which is key (``kv_lora_rank + qk_rope_head_dim`` values) and value (its
first ``kv_lora_rank``) at once, one call a layer and step.

Needs, per cached token of a (row, layer, step):
- bytes: the latent ONCE at the width the model states, ``(kv_lora_rank +
  qk_rope_head_dim) * itemsize`` = 1152 (a pool that holds it 640 lanes
  wide moves a ninth more and reads that much below its share; a path
  that reads a page for the keys and again for the values reads at half
  of it);
- operations: ``2 * heads * ((kv_lora_rank + qk_rope_head_dim) +
  kv_lora_rank)`` = 278.5 k for the absorbed scores and values.
At 128 heads that is 241.8 operations a byte against the chip's 240.5:
the ridge, so the least time is the greater of the two and neither the
matrix unit nor the memory may wait for the other. The counts state the
work, whatever implements it.
"""


def needs(ctx_tokens, cfg, itemsize=2):
    """(operations, bytes) of calls that read ``ctx_tokens`` cached
    latents in all (summed over rows, layers and steps)."""
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ops = 2.0 * ctx_tokens * cfg["num_attention_heads"] \
        * (latent + cfg["kv_lora_rank"])
    return ops, float(ctx_tokens) * latent * itemsize


def least_seconds(ctx_tokens, cfg, peaks, itemsize=2):
    ops, nbytes = needs(ctx_tokens, cfg, itemsize)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

"""The decode step of learned sparse attention over a latent cache
(paddle_tpu/models/glm_moe_dsa.py, ``_decode_attention``): for one query
token per live row, the indexer scores every cached token of the row
against the row's indexer keys, the ``index_topk`` best are kept, and the
absorbed query (all heads against ONE latent a token) reads the chosen
latents alone, one call a layer and step.

Needs, per (row, layer, step) with ``context`` cached tokens of which
``selected = min(context, index_topk)`` are read:
- bytes: every indexer key once, ``context * index_head_dim * itemsize``,
  and every chosen latent once, ``selected * (kv_lora_rank +
  qk_rope_head_dim) * itemsize``, at the widths the model states (a pool
  that pads the latent of 576 to 640 lanes moves a ninth more of it, and
  reads that much below its share; a path that reads the whole latent
  context and not the chosen tokens reads ``context / selected`` times
  the second term);
- operations: ``context * 2 * index_n_heads * index_head_dim`` for the
  scores and ``selected * heads * 2 * ((kv_lora_rank + qk_rope_head_dim)
  + kv_lora_rank)`` for the absorbed scores and values.
Bound: bytes for the indexer (32 heads x 128 a key of 256 B: 32
operations a byte against the chip's 240), operations for the read of
the chosen latents at 64 heads (121 a byte). The counts state the work,
whatever implements it.
"""


def needs(scored_tokens, selected_tokens, cfg, itemsize=2):
    """(operations, bytes) of calls that score ``scored_tokens`` cached
    tokens and read ``selected_tokens`` chosen ones in all (each summed
    over rows, layers and steps)."""
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ops = (2.0 * scored_tokens * cfg["index_n_heads"] * cfg["index_head_dim"]
           + 2.0 * selected_tokens * cfg["num_attention_heads"]
           * (latent + cfg["kv_lora_rank"]))
    nbytes = (float(scored_tokens) * cfg["index_head_dim"]
              + float(selected_tokens) * latent) * itemsize
    return ops, nbytes


def least_seconds(scored_tokens, selected_tokens, cfg, peaks, itemsize=2):
    ops, nbytes = needs(scored_tokens, selected_tokens, cfg, itemsize)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

"""The cold prefill of learned sparse attention over a latent cache
(paddle_tpu/models/glm_moe_dsa.py, ``_block_attention``): for a prompt of
``n`` tokens and each layer, the indexer scores every (query, earlier
token) pair, each query keeps its ``index_topk`` best, and the main
attention runs over the kept pairs alone.

Needs, a layer:
- scored pairs ``n (n + 1) / 2`` at ``2 * index_n_heads *
  index_head_dim`` operations each;
- attended pairs ``sum_t min(t + 1, index_topk)`` at ``heads * 2 *
  ((qk_nope_head_dim + qk_rope_head_dim) + v_head_dim)`` each: the
  EXPANDED form's count, the lesser of the two forms (the absorbed form
  does ``heads * 2 * (576 + 512)`` a pair and saves the expansion of the
  keys and values; a masked dense pass does the context's pairs, not the
  kept ones): a program in either reads below its share.
Bound: operations, over the chip's bfloat16 peak; the keys and latents a
block reads are reused by its 256 queries. The counts state the work,
whatever implements it.
"""


def pairs(n, index_topk):
    """(scored, attended) pairs of one layer over the first ``n`` tokens
    of a prompt (``n`` may be a fraction: a launch cut by a trace)."""
    full = min(n, index_topk)
    return (n * (n + 1) / 2.0,
            full * (full + 1) / 2.0 + (n - full) * index_topk)


def operations(scored_pairs, attended_pairs, cfg):
    wide = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
    return (2.0 * scored_pairs * cfg["index_n_heads"] * cfg["index_head_dim"]
            + 2.0 * attended_pairs * cfg["num_attention_heads"] * wide)


def least_seconds(scored_pairs, attended_pairs, cfg, peaks):
    return operations(scored_pairs, attended_pairs, cfg) \
        / peaks["bf16_flops_per_s"]

"""Flash attention forward (paddle_tpu/kernels/flash_attention.py,
``flash_attention_fwd``), causal, grouped-query.

Needs, for ``batch`` sequences of ``seq`` tokens:
- operations: ``4 * batch * heads * head_dim * seq * seq / 2`` (QK^T and
  PV over the causal half);
- bytes: q and the output once, k and v once:
  ``batch * seq * head_dim * (2 * heads + 2 * kv_heads) * itemsize``.
Bound: FLOPs from a few hundred tokens on (operations per byte grow
with ``seq``: about ``seq / 2`` at 28 heads over 4 kv heads).

No cell reads it yet: the serving engine's prefill runs XLA attention
(``_attention_keymask``), not this kernel (PERF.md, Findings, PR 23).
"""


def needs(batch, seq, heads, kv_heads, head_dim, itemsize=2):
    ops = 4.0 * batch * heads * head_dim * seq * seq / 2.0
    nbytes = float(batch * seq * head_dim * (2 * heads + 2 * kv_heads) * itemsize)
    return ops, nbytes


def least_seconds(batch, seq, heads, kv_heads, head_dim, peaks, itemsize=2):
    ops, nbytes = needs(batch, seq, heads, kv_heads, head_dim, itemsize)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

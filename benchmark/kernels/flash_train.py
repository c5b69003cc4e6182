"""Flash attention forward and backward in one training step, causal,
grouped-query, as the step needs it (no recomputation counted).

- operations: forward ``4 * b * h * hd * s * s / 2``; backward computes
  dQ, dK, dV and re-forms the probabilities, 2.5 times the forward's
  matmuls in the standard flash backward: taken as ``2 * forward`` needed
  (dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q; the recomputed QK^T
  is not needed work), so ``3 * forward`` in all;
- bytes: forward's, plus dO, dQ read or written once and dK, dV once.
Bound: FLOPs at the cells' sequence lengths.
"""

from benchmark.kernels import flash_fwd


def needs(batch, seq, heads, kv_heads, head_dim, itemsize=2):
    f_ops, f_bytes = flash_fwd.needs(batch, seq, heads, kv_heads, head_dim,
                                     itemsize)
    b_bytes = float(batch * seq * head_dim * (3 * heads + 4 * kv_heads) * itemsize)
    return 3.0 * f_ops, f_bytes + b_bytes


def least_seconds(batch, seq, heads, kv_heads, head_dim, peaks, itemsize=2):
    ops, nbytes = needs(batch, seq, heads, kv_heads, head_dim, itemsize)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

"""Paged decode attention (paddle_tpu/kernels/paged_attention.py,
``paged_decode_attention``): one query token per live row against that
row's cached keys and values.

Needs, per call (one layer, one step), for rows of cached lengths
``lens``:
- bytes: every cached key and value once, ``sum(lens) * kv_heads *
  head_dim * 2 * itemsize``, plus the queries and outputs
  (``2 * rows * heads * head_dim`` values);
- operations: ``4 * sum(lens) * heads * head_dim`` (QK^T and PV).
Bound: bytes. At 7 query heads per kv head the kernel does 14 operations
per byte read, far under the chip's 240 operations per byte.
"""


def needs(lens, heads, kv_heads, head_dim, itemsize=2):
    """(operations, bytes) one call needs."""
    tokens = float(sum(lens))
    rows = len(lens)
    nbytes = (tokens * kv_heads * head_dim * 2 * itemsize
              + 2 * rows * heads * head_dim * itemsize)
    ops = 4.0 * tokens * heads * head_dim
    return ops, nbytes


def least_seconds(lens, heads, kv_heads, head_dim, peaks, itemsize=2):
    ops, nbytes = needs(lens, heads, kv_heads, head_dim, itemsize)
    return max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

"""The decode read of a paged cache whose key and value heads differ in
width (paddle_tpu/kernels/paged_attention.py, ``paged_attention_pallas``
with K and V pools of their own widths): one query token per live row
against that row's cached keys and values, one call a layer and step.

Needs, per call, for rows whose cached lengths add up to ``ctx_tokens``:
- bytes: every cached key and value once, ``ctx_tokens * kv_heads *
  (key_width + value_width) * itemsize``, at the widths the model
  states (a pool that pads a key head of 192 to 256 lanes moves a fifth
  more, and reads that much below its share);
- operations: ``2 * ctx_tokens * heads * (key_width + value_width)``.
Bound: bytes, at 16 query heads a kv head still 32 operations a byte
against the chip's 240. ``benchmark/kernels/paged_decode.py`` is the
count for pools of one width.
"""


def needs(ctx_tokens, heads, kv_heads, key_width, value_width, itemsize=2):
    """(operations, bytes) of calls that read ``ctx_tokens`` cached
    tokens in all."""
    wide = key_width + value_width
    return (2.0 * ctx_tokens * heads * wide,
            float(ctx_tokens) * kv_heads * wide * itemsize)


def least_seconds(ctx_tokens, heads, kv_heads, key_width, value_width, peaks,
                  itemsize=2):
    ops, nbytes = needs(ctx_tokens, heads, kv_heads, key_width, value_width,
                        itemsize)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

"""The expert products of a routed SwiGLU layer
(paddle_tpu/distributed/fleet/moe.py, ``moe_dropless_ffn``: three grouped
products over the expert-sorted pairs), whatever implements them.

Needs, for ``pairs`` (token, expert) pairs that visit ``visits`` experts
(an expert with at least one pair, counted once a layer and launch):
- bytes: a visited expert's three matrices once, ``3 * hidden * width *
  itemsize`` (50.3 MB at 4096 x 2048 in bfloat16); a pair's row of
  ``hidden`` values in and out. An expert no token chose need not be
  read: an implementation that reads it reads below its share;
- operations: ``6 * hidden * width`` a pair (three products, a multiply
  and an add each).
Bound: bytes while an expert sees fewer than some 240 pairs (a decode
step's few rows, a prefill block's 8 an expert); operations beyond.
"""


def needs(pairs, visits, hidden, width, itemsize=2):
    """(operations, bytes) of the expert products."""
    nbytes = (visits * 3.0 * hidden * width + pairs * 2.0 * hidden) * itemsize
    return 6.0 * hidden * width * pairs, nbytes


def least_seconds(pairs, visits, hidden, width, peaks, itemsize=2):
    ops, nbytes = needs(pairs, visits, hidden, width, itemsize)
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])

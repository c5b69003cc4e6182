"""Which ``k`` of one row's scores are the largest, as a mask and without
a sort, for a decode step's selection over ONE row's cached tokens
(``models/glm_moe_dsa.py``): the ``k``-th largest score found bit by bit
(32 counting passes over keys that order as the float32 scores do), then
of the scores equal to it the earliest, as many as there is room for (a
second search, over the column). What ``lax.top_k`` keeps, what
``glm_moe_dsa._chosen_mask`` gives for the prefill's blocks of rows by
the same passes in XLA; there a pass reads a block's scores from memory,
here a row's few thousand scores sit in fast memory for all of them and
the selection is ONE launch: 8-11 us a row and layer on a v5e where the
loop in XLA takes 26-41 (PERF.md, Findings PR 43).

- :func:`chosen_mask_pallas`: ``name="dsa_topk_mask"`` in a device trace.
- the CPU path and the kernel's oracle is ``_chosen_mask`` itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "dsa_topk_mask"
_LANES = 128
_TILE = 8 * _LANES          # a row is laid out in whole (8, 128) tiles
_LOWEST = -2 ** 31


def _kernel(k, pos_ref, scores_ref, out_ref):
    rows, lanes = scores_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    seen = col <= pos_ref[0]
    lowest = jnp.int32(_LOWEST)
    bits = pltpu.bitcast(scores_ref[...], jnp.int32)
    # int32 keys in the order of the float32 scores; no column past the
    # row's last counts
    keys = jnp.where(seen, jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF),
                                     bits), lowest)
    count = lambda m: jnp.sum(m.astype(jnp.int32))

    def key_bit(i, prefix):     # the prefix orders as unsigned
        cand = prefix | jax.lax.shift_left(jnp.int32(1), 31 - i)
        return jnp.where(count(keys >= (cand ^ lowest)) >= k, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, key_bit, jnp.int32(0)) ^ lowest
    above, tie = keys > kth, keys == kth
    room = k - count(above)
    n_bits = (rows * lanes).bit_length()

    def col_bit(i, upto):       # the most columns whose ties still fit
        cand = upto | jax.lax.shift_left(jnp.int32(1), n_bits - 1 - i)
        return jnp.where(count(tie & (col < cand)) <= room, cand, upto)

    upto = jax.lax.fori_loop(0, n_bits, col_bit, jnp.int32(0))
    out_ref[...] = ((above | (tie & (col < upto))) & seen).astype(jnp.int32)


def chosen_mask_pallas(scores, pos, k, interpret=False):
    """scores [1, w] float32 of one row whose last cached token stands at
    column ``pos`` [1] -> [1, w] bool: the ``k`` largest of columns
    ``0..pos`` (all of them while there are no more than ``k``), of
    equal scores the earliest."""
    w = scores.shape[1]
    padded = -(-w // _TILE) * _TILE
    tiles = jnp.pad(scores, ((0, 0), (0, padded - w))).reshape(-1, _LANES)
    mask = pl.pallas_call(
        functools.partial(_kernel, k),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret, name=KERNEL_NAME)(pos.astype(jnp.int32), tiles)
    return mask.reshape(1, padded)[:, :w] != 0


def kernel_serves():
    """The kernel on the TPU backend; ``_chosen_mask`` elsewhere."""
    return jax.default_backend() == "tpu"

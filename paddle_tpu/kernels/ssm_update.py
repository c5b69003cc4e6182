"""One token's step of a Mamba-2 layer's recurrent states, for the live
rows of a decode batch only.

    S[r] <- a[r] * S[r] + dt[r] x[r] (x) B[r];   y[r] = S[r] C[r]

The states are the engine's: ``[Lm, slots, H, n, W]`` float32, stacked
over the Mamba layers and the slots, donated and updated in place. A
row's state is stored with the state dimension ``n`` ahead of the head
dimension and ``pack`` heads side by side on the lanes (``W = pack *
head_dim``, ``H = heads / pack``): a head of 64 fills half a lane tile,
two fill one, and everything the step needs is then a row over the
lanes (decay and ``dt x``, one value a (head, channel)) or a column over
the sublanes (``B`` and ``C``, one value a state index), so the update
is a broadcast multiply-add and ``y`` a reduction over sublanes.

- :func:`ssm_update_pallas`: one program a live row (``name=
  "ssm_decode_update"`` in a device trace). The rows to visit ride the
  scalar-prefetch lane, compacted, with the layer; a program past the
  last live row maps to the block before it and does nothing, so no
  state of a slot that holds no row is read or written: the cost follows
  the live rows, not the batch (``PERF.md``, Findings 1).
- :func:`ssm_update_reference`: the same arithmetic in XLA over all rows,
  dead rows masked; the CPU path and the kernel's oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "ssm_decode_update"


def lane_pack(heads, head_dim):
    """Heads stored side by side on the 128 lanes."""
    pack = max(1, 128 // head_dim)
    while heads % pack:
        pack //= 2
    return pack


def pack_state(state, pack):
    """[..., heads, head_dim, n] -> the stored [..., heads/pack, n,
    pack*head_dim]."""
    *lead, h, p, n = state.shape
    s = state.reshape(*lead, h // pack, pack, p, n)
    s = jnp.moveaxis(s, -1, -3)                     # [..., H, n, pack, p]
    return s.reshape(*lead, h // pack, n, pack * p)


def _operands(xs, dt, a, pack):
    """Per (head, channel) rows over the lanes: the decay and dt * x,
    [b, H, W] float32."""
    b, h, p = xs.shape
    shape = (b, h // pack, pack * p)
    return (jnp.broadcast_to(a[:, :, None], xs.shape).reshape(shape),
            (dt[:, :, None] * xs).reshape(shape))


def ssm_update_reference(ssm, layer, xs, dt, a, bm, cm, live):
    """ssm [Lm, b, H, n, W]; xs [b, heads, head_dim], dt/a [b, heads],
    bm/cm [b, n] float32; live [b] bool. Returns (ssm, y [b, heads,
    head_dim]); a row that is not live keeps its state and reads y = 0."""
    pack = xs.shape[1] // ssm.shape[2]
    a_row, dtx = _operands(xs, dt, a, pack)
    old = ssm[layer]
    new = a_row[:, :, None, :] * old \
        + bm[:, None, :, None] * dtx[:, :, None, :]
    y = (new * cm[:, None, :, None]).sum(axis=2)
    keep = live[:, None, None, None]
    ssm = jax.lax.dynamic_update_index_in_dim(
        ssm, jnp.where(keep, new, old), layer, 0)
    return ssm, jnp.where(live[:, None, None], y.reshape(xs.shape), 0.0)


def _kernel(layer, rows, n_live, s_ref, a_ref, dtx_ref, b_ref, c_ref,
            y0_ref, so_ref, y_ref):
    @pl.when(pl.program_id(0) < n_live[0])
    def _row():
        b_col = b_ref[0]                                # [n, 1]
        c_col = c_ref[0]

        def head(j, carry):
            s = a_ref[0, pl.ds(j, 1), :] * s_ref[0, 0, j] \
                + b_col * dtx_ref[0, pl.ds(j, 1), :]    # [n, W]
            so_ref[0, 0, j] = s
            y_ref[0, pl.ds(j, 1), :] = jnp.sum(s * c_col, axis=0,
                                               keepdims=True)
            return carry

        jax.lax.fori_loop(0, s_ref.shape[2], head, 0)


def ssm_update_pallas(ssm, layer, xs, dt, a, bm, cm, live, interpret=False):
    """:func:`ssm_update_reference` as one program a live row. A row's
    whole state ``[H, n, W]`` is one block, fetched from and written
    back to ``[layer, row]`` of the aliased states."""
    lm, b, hh, n, w = ssm.shape
    pack = xs.shape[1] // hh
    a_row, dtx = _operands(xs, dt, a, pack)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = live.sum().astype(jnp.int32)
    # past the last live row: stay on its block (nothing moves)
    rows = jnp.where(jnp.arange(b) < n_live, order,
                     order[jnp.maximum(n_live - 1, 0)])

    def row_block(*tail):
        return pl.BlockSpec((1,) + tail,
                            lambda i, layer, rows, n: (rows[i],)
                            + (0,) * len(tail))

    state_spec = pl.BlockSpec(
        (1, 1, hh, n, w),
        lambda i, layer, rows, n: (layer[0], rows[i], 0, 0, 0))
    block_bytes = hh * n * w * 4
    ssm, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[state_spec, row_block(hh, w), row_block(hh, w),
                      row_block(n, 1), row_block(n, 1), row_block(hh, w)],
            out_specs=[state_spec, row_block(hh, w)]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((b, hh, w), jnp.float32)],
        # inputs count the three scalar-prefetch operands
        input_output_aliases={3: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(4 * block_bytes + (8 << 20))),
        interpret=interpret, name=KERNEL_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, n_live.reshape(1),
      ssm, a_row, dtx, bm[:, :, None], cm[:, :, None],
      jnp.zeros((b, hh, w), jnp.float32))
    return ssm, y.reshape(xs.shape)


def kernel_serves(ssm):
    """The kernel on the TPU backend when a row's state tiles (a state
    index a sublane, a lane tile of channels); the reference elsewhere."""
    n, w = ssm.shape[-2:]
    return jax.default_backend() == "tpu" and w % 128 == 0 and n % 8 == 0


def ssm_decode_update(ssm, layer, xs, dt, a, bm, cm, live):
    """Entry used by the Mamba-2 decode step."""
    fn = ssm_update_pallas if kernel_serves(ssm) else ssm_update_reference
    return fn(ssm, layer, xs, dt, a, bm, cm, live)

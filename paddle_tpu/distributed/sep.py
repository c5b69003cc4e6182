"""Long-context attention over the 'sep' mesh axis.

The reference's sep axis (fleet/base/topology.py:64,184,226 + SegmentParallel
meta_parallel/segment_parallel.py:26 + four_directions_p2p_communication.py)
shards the sequence across workers but ships no library attention op — the
model must cooperate. SURVEY §5 mandates the TPU build supply a real one:

- ``ring_attention``: K/V blocks rotate around the sep ring via
  ``lax.ppermute`` (ICI neighbor exchange) while each shard's queries
  accumulate with an online softmax — FlashAttention-style streaming where
  the "blocks" are whole shards. Memory per chip is O(S/n), comm is the
  bandwidth-optimal ring. (RingAttention, Liu et al.; blockwise parallel
  transformers.)
- ``ulysses_attention``: DeepSpeed-Ulysses-style all-to-all head-scatter —
  seq-sharding is exchanged for head-sharding, each chip runs full-sequence
  flash attention on H/n heads, and a reverse all-to-all restores the seq
  sharding. Cheaper at moderate S (two all-to-alls vs n-1 permutes) but
  requires num_kv_heads % sep == 0.

Both run INSIDE the jitted program as ``jax.shard_map`` regions manual over
{'sep'} only — dp/mp stay on GSPMD auto, so TP head-sharding composes with
sequence sharding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ulysses_attention", "sep_attention",
           "ring_attention_local", "mesh_flash_attention"]

_NEG_INF = -1e30


def _grouped(x):
    """[b, s, h, d] -> [b, hkv(=h), s, d] head-major."""
    return jnp.swapaxes(x, 1, 2)


def ring_attention_local(q, k, v, axis_name: str, n_shards: int,
                         causal: bool = True):
    """Per-shard ring attention body (call inside shard_map over
    ``axis_name``). q: [b, sq, h, d]; k, v: [b, sk, hkv, d] — all local
    shards of a sequence laid out in contiguous blocks (GSPMD 'sep'
    sharding). Returns the local output [b, sq, h, d].

    BLOCKWISE (VERDICT #4): each hop runs the flash kernel on the local
    (q, k_hop, v_hop) pair, producing (out, lse); hops combine with an
    online softmax over the lse — per-hop memory is O(sq·d), never the
    full [sq, sk] score matrix. The lse path is differentiable
    (kernels.flash_attention.attention_with_lse folds the lse cotangent
    into the FA2 backward)."""
    from ..kernels.flash_attention import attention_with_lse
    b, sq, h, d = q.shape
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    # hop 0: the local block — ordinary causal (or full) attention
    out0, lse0 = attention_with_lse(q, k, v, causal=causal)
    out0 = out0.astype(jnp.float32)

    def step(carry, t):
        k_cur, v_cur, lse_run, out_run = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        # after t hops my held block originated on rank (my - t) mod n
        src = (my - t) % n_shards
        out_h, lse_h = attention_with_lse(q, k_cur, v_cur, causal=False)
        if causal:
            # blocks strictly earlier attend fully; later (wrapped)
            # blocks contribute nothing (weight exp(-inf) = 0)
            valid = src < my
            lse_h = jnp.where(valid, lse_h, _NEG_INF)
        new_lse = jnp.logaddexp(lse_run, lse_h)
        w_old = jnp.exp(lse_run - new_lse)              # [b*h, 1, sq]
        w_new = jnp.exp(lse_h - new_lse)
        wo = jnp.swapaxes(w_old.reshape(b, h, sq), 1, 2)[..., None]
        wn = jnp.swapaxes(w_new.reshape(b, h, sq), 1, 2)[..., None]
        out_run = out_run * wo + out_h.astype(jnp.float32) * wn
        return (k_cur, v_cur, new_lse, out_run), None

    (_, _, _, out), _ = lax.scan(
        step, (k, v, lse0, out0), jnp.arange(1, n_shards))
    return out.astype(q.dtype)


def _seq_spec(axis_name):
    """[b, s, h, d] with the seq dim over the sep axis."""
    from jax.sharding import PartitionSpec as P
    return P(None, axis_name, None, None)


def ring_attention(q, k, v, causal: bool = True, axis_name: str = "sep",
                   mesh=None):
    """Ring attention on full [b, s, h, d] arrays whose seq dim is (to be)
    sharded over ``axis_name``. Works under jit with a GSPMD mesh; falls
    back to plain attention when the axis is absent or size 1."""
    mesh = mesh or _current_mesh()
    n = _axis_size(mesh, axis_name)
    if n <= 1:
        from ..kernels.flash_attention import _sdpa_reference
        return _sdpa_reference(q, k, v, causal)
    spec = _seq_spec(axis_name)
    fn = functools.partial(ring_attention_local, axis_name=axis_name,
                           n_shards=n, causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis_name})(q, k, v)


def ulysses_attention(q, k, v, causal: bool = True, axis_name: str = "sep",
                      mesh=None):
    """All-to-all (Ulysses) attention: trade seq-sharding for head-sharding,
    run full-sequence flash attention locally, trade back."""
    mesh = mesh or _current_mesh()
    n = _axis_size(mesh, axis_name)
    if n <= 1:
        from ..kernels.flash_attention import _sdpa_reference
        return _sdpa_reference(q, k, v, causal)
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"ulysses_attention needs heads ({q.shape[2]}) and kv heads "
            f"({k.shape[2]}) divisible by sep={n}; use ring_attention")
    spec = _seq_spec(axis_name)

    def local(q, k, v):
        # [b, s/n, h, d] -> [b, s, h/n, d]
        q = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
        k = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
        v = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
        from ..kernels.flash_attention import flash_attention_fwd
        out = flash_attention_fwd(q, k, v, causal=causal)
        return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    # check_vma off: pallas_call inside shard_map can't express output vma
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis_name},
                         check_vma=False)(q, k, v)


def mesh_flash_attention(q, k, v, causal: bool = True, mesh=None):
    """The flash kernel under a GSPMD mesh (no sep axis in play). The
    compiler refuses to split a Pallas kernel by itself ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map"), so run it per shard of the layout the model's hints on
    q/k/v already ask for: batch over dp, heads — query and kv alike —
    over mp. A mesh with neither axis > 1 runs the kernel as it is."""
    from ..kernels.flash_attention import flash_attention_fwd
    from .fleet.mp_layers import _filter_spec
    fn = functools.partial(flash_attention_fwd, causal=causal)
    spec = _filter_spec(("dp", None, "mp", None), mesh)
    axes = {a for a in spec if a is not None}
    if not axes:
        return fn(q, k, v)
    # check_vma off: pallas_call inside shard_map can't express output vma
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=axes,
                         check_vma=False)(q, k, v)


def sep_attention(q, k, v, causal: bool = True, axis_name: str = "sep",
                  mesh=None, mode: str | None = None):
    """Dispatch: the library attention op over a sep-sharded sequence
    (discharges the SegmentParallel promise — reference ships none). mode in
    {'ring', 'alltoall', None=auto}: auto picks alltoall when heads divide
    evenly (cheaper comm), else ring."""
    mesh = mesh or _current_mesh()
    n = _axis_size(mesh, axis_name)
    if mode is None:
        from .. import flags
        mode = flags.flag("sep_attention_mode")
    if mode == "alltoall" or (mode == "auto" and n > 1
                              and q.shape[2] % n == 0
                              and k.shape[2] % n == 0):
        return ulysses_attention(q, k, v, causal, axis_name, mesh)
    return ring_attention(q, k, v, causal, axis_name, mesh)


def _current_mesh():
    from .fleet.mp_layers import current_mesh
    return current_mesh()


def _axis_size(mesh, axis_name) -> int:
    if mesh is None or axis_name not in getattr(mesh, "axis_names", ()):
        return 1
    return mesh.shape[axis_name]

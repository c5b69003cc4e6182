"""Sequence parallelism utilities (reference: fleet/utils/
sequence_parallel_utils.py — scatter/allgather/reduce-scatter PyLayers
:83-141, ColumnSequenceParallelLinear:228, RowSequenceParallelLinear:338,
mark_as_sequence_parallel_parameter:146).

TPU-native: Megatron-SP = activations sharded on the sequence dim over the
'mp' axis between TP regions — a sharding annotation; GSPMD inserts the
allgather before column-parallel matmuls and reduce-scatter after
row-parallel ones. The 'sep' long-context axis (SegmentParallel) is handled
in paddle_tpu.distributed.sep (ring attention / all-to-all)."""

from __future__ import annotations

from ...core.tensor import Tensor
from ... import nn
from ...nn import functional as F
from .mp_layers import shard_hint

__all__ = ["scatter", "all_gather", "mark_as_sequence_parallel_parameter",
           "is_sequence_parallel_parameter",
           "register_sequence_parallel_allreduce_hooks",
           "ColumnSequenceParallelLinear", "RowSequenceParallelLinear",
           "GatherOp", "ScatterOp", "AllGatherOp", "ReduceScatterOp"]


def scatter(input):
    """Split activations along seq dim across mp ranks (reference :83
    ScatterOp) — here a resharding hint [b, s/mp, h]."""
    return shard_hint(input, "dp", "mp", None)


def all_gather(input):
    """Gather seq-sharded activations (reference AllGatherOp)."""
    return shard_hint(input, "dp", None, None)


class GatherOp:
    """reference :83 GatherOp/AllGatherOp — gather the seq-sharded dim."""
    apply = staticmethod(all_gather)


AllGatherOp = GatherOp


class ScatterOp:
    """reference ScatterOp — split a REPLICATED activation along seq."""
    apply = staticmethod(scatter)


class ReduceScatterOp:
    """reference ReduceScatterOp — reduce an mp-PARTIAL activation and
    scatter the result along seq. In the GSPMD auto path the annotation is
    the same as ScatterOp (partiality lives on the producer, XLA inserts
    the reduction); the explicitly-wired reduce-scatter — one psum_scatter
    on the wire instead of all-reduce+slice — is the shard_map path inside
    RowSequenceParallelLinear.forward."""
    apply = staticmethod(scatter)


_SP_PARAMS: set[int] = set()


def mark_as_sequence_parallel_parameter(parameter):
    """reference :146 — LN/bias params replicated across mp but living in
    the SP region; under GSPMD their grads are already correctly psummed, we
    keep the mark for parity and checkpoint tools."""
    _SP_PARAMS.add(id(parameter))


def is_sequence_parallel_parameter(parameter):
    return id(parameter) in _SP_PARAMS


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_sequence_parallel_allreduce=False):
    """reference :190 — in the reference, SP-region LN/bias params hold
    disjoint per-rank grads that need an mp-group allreduce. Here model
    parallelism lives inside compiled GSPMD programs (grads are global
    arrays) and eager multi-process params are replicated with DP-hook
    syncing — there is no process-level mp shard to reduce over, so this
    is a true no-op kept for recipe compatibility."""
    return model


class ColumnSequenceParallelLinear(nn.Layer):
    """reference :228 — input seq-sharded, allgather(seq) then column matmul."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=False, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self._gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=nn.initializer.XavierNormal())
        self.weight._dist_spec = (None, "mp")
        if has_bias in (True, None):
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)
            self.bias._dist_spec = ("mp",)
        else:
            self.bias = None

    def forward(self, x):
        x = all_gather(x)  # [b, s, h] replicated on seq
        out = F.linear(x, self.weight, self.bias)
        if self._gather_output:
            return shard_hint(out, "dp", None, None)
        return shard_hint(out, "dp", None, "mp")


class RowSequenceParallelLinear(nn.Layer):
    """reference :338 — row matmul then reduce-scatter onto seq dim."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=True,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=nn.initializer.XavierNormal())
        self.weight._dist_spec = ("mp", None)
        if has_bias:
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        """Row-parallel matmul + REAL reduce-scatter onto the seq dim:
        when an mp>1 mesh is active and shapes tile, the contraction runs
        inside shard_map manual over {'mp'} and finishes with ONE
        lax.psum_scatter (half the bytes of GSPMD's all-reduce+slice
        fallback, which this path was measured to emit otherwise)."""
        import jax
        from jax.sharding import PartitionSpec as P
        from .mp_layers import current_mesh
        mesh = current_mesh()
        mp = (mesh.shape["mp"] if mesh is not None
              and "mp" in getattr(mesh, "axis_names", ()) else 1)
        xv = x._value if isinstance(x, Tensor) else x
        seq_ok = xv.ndim == 3 and xv.shape[1] % max(mp, 1) == 0
        if mp > 1 and seq_ok and self.weight.shape[0] % mp == 0:
            def local(xl, wl):
                partial = xl @ wl                  # [b, s, out] mp-partial
                return jax.lax.psum_scatter(partial, "mp",
                                            scatter_dimension=1,
                                            tiled=True)  # [b, s/mp, out]

            from ...core.dispatch import apply_op

            def f(xr, wr):
                out = jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(P(None, None, "mp"), P("mp", None)),
                    out_specs=P(None, "mp", None),
                    axis_names={"mp"})(xr, wr)
                return out

            out = apply_op("row_sp_linear", f, (x, self.weight), {})
            if self.bias is not None:
                out = out + self.bias
            return out
        out = F.linear(x, self.weight, self.bias)
        return scatter(out)  # GSPMD fallback: hint; XLA inserts the reduce

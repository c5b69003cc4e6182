"""Device wiring for the sharded DecodeEngine: the ISSUE 10 1-D
tensor-parallel mesh plus the ISSUE 16 second (``seq``) axis
(reference shape: GSPMD sharding annotations + shard_map-lowered
programs, PAPERS.md, and the Megatron column/row pattern already
manual-coded in ``models/llama.py``).

Design (SURVEY §7.17 for tp, §7.22 for the 2-D mesh):

- What SHARDS over ``tp``: the paged KV block pools
  ``[L, N, kvh, bs, hd]`` carry a ``PartitionSpec`` over the kv-head
  axis (axis 2), the int8 page scales ``[L, N, kvh]`` shard alongside
  on their kvh axis, and the attention/MLP weights shard column/row
  Megatron-style (head and ff columns split, ``wo``/``w_down`` rows
  split and psum-finished inside the program). Embedding, norms,
  router, and lm_head replicate.
- What SHARDS over ``seq``: the pools' PAGE axis (axis 1). Each seq
  shard holds ``N/seq`` pages and attends only over pages it owns;
  attention finishes with one online-softmax partial merge
  (max/sum/weighted-V, ring-attention math over a flat topology) along
  ``seq``. Weights replicate over ``seq``; long prefills spread their
  chunk windows across it (context parallelism), so
  ``tp × seq > n_kv_heads`` becomes legal.
- What REPLICATES: block tables, lens, ids windows — host-side data.
- Why the allocator stays HOST-SIDE: page ids index the pool's
  GLOBAL N axis, so one allocation decision is valid on every shard —
  allocation, COW, preemption, chunked prefill, and quarantine
  semantics are device-count-independent and carry over from r7–r14
  unchanged. Under a 2-D mesh the allocator stripes pages so table
  column ``j`` always lands in stripe ``j % seq`` (paged_cache.py),
  keeping the per-shard strided gather dense; it still holds no tensor
  data and needs no coherence protocol.

The programs themselves lower through ``jit`` + ``jax.shard_map``; this
module only builds meshes and the PartitionSpec pytrees the engine
feeds those calls.
"""

from __future__ import annotations

from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["TP_AXIS", "SEQ_AXIS", "make_tp_mesh", "make_mesh",
           "validate_tp_config", "validate_mesh_config",
           "stacked_weight_specs", "quant_scale_specs", "pool_specs",
           "same_pool_placement"]

TP_AXIS = "tp"
SEQ_AXIS = "seq"

# Megatron layout over the stacked [L, ...] parameter tree:
# column-parallel weights split their OUTPUT features (heads / ff
# columns), row-parallel weights split the matching CONTRACTION axis
# and their matmuls finish with a psum inside the program.
_COL_LAST = ("wq", "wk", "wv", "w_gate", "w_up", "ws_gate", "ws_up")
_COL_BIAS = ("bq", "bk", "bv")
_ROW_AXIS1 = ("wo", "w_down", "ws_down")
_EXPERT_COL = ("we_gate", "we_up")      # [L, E, d, ff] — split ff
_EXPERT_ROW = ("we_down",)              # [L, E, ff, d] — split ff


def make_tp_mesh(tp_degree, devices=None, axis=TP_AXIS):
    """A 1-D mesh of ``tp_degree`` devices for the sharded engine.
    ``devices``: explicit device list (the fleet carves submeshes out
    of ``jax.devices()`` this way); default takes the first
    ``tp_degree`` global devices."""
    import jax
    import numpy as np
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < tp_degree:
        raise ValueError(
            f"tp_degree={tp_degree} needs {tp_degree} devices, have "
            f"{len(devs)}")
    return Mesh(np.asarray(devs[:tp_degree]), (axis,))


def make_mesh(tp_degree, seq_degree=1, devices=None, tp_axis=TP_AXIS,
              seq_axis=SEQ_AXIS):
    """A 2-D ``(seq, tp)`` mesh of ``seq_degree × tp_degree`` devices.
    ``seq`` is the outer axis (page/context parallelism), ``tp`` the
    inner (kv-head/Megatron parallelism) — the inner axis gets the
    tighter device grouping, matching the heavier per-layer psum
    traffic tp carries. ``seq_degree=1`` still builds a 2-D mesh whose
    seq extent is 1; callers wanting the exact r15 1-D mesh use
    :func:`make_tp_mesh`."""
    import jax
    import numpy as np
    tp = int(tp_degree)
    sq = int(seq_degree)
    if tp < 1 or sq < 1:
        raise ValueError(f"tp_degree={tp_degree}, seq_degree={seq_degree}")
    need = tp * sq
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"tp_degree={tp} x seq_degree={sq} needs {need} devices, "
            f"have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(sq, tp)
    return Mesh(grid, (seq_axis, tp_axis))


def validate_tp_config(cfg, tp):
    """Divisibility the kv-head sharding requires (1-D form; delegates
    to :func:`validate_mesh_config` with ``seq=1``)."""
    validate_mesh_config(cfg, tp)


def validate_mesh_config(cfg, tp, seq=1, n_blocks=None):
    """Divisibility the 2-D mesh requires. Checked at engine
    construction so a bad degree fails loudly instead of as a cryptic
    shard_map shape error. Reports ALL violated constraints in one
    message, and names the ``seq`` axis as the escape hatch when
    ``tp`` exceeds the kv-head count outright."""
    if tp < 1:
        raise ValueError(f"tp_degree={tp}")
    if seq < 1:
        raise ValueError(f"seq_degree={seq}")
    problems = []
    kvh = cfg.num_key_value_heads
    if kvh % tp:
        msg = (f"num_key_value_heads={kvh} not divisible by tp={tp} "
               f"(the KV pool shards over kv heads)")
        if tp > kvh:
            msg += (f"; tp={tp} exceeds the {kvh} kv heads outright — "
                    f"shard the page axis instead: a 2-D mesh "
                    f"(make_mesh) with tp_degree<={kvh} and "
                    f"seq_degree>1 lifts the device count past the "
                    f"kv-head cap")
        problems.append(msg)
    if cfg.num_attention_heads % tp:
        problems.append(
            f"num_attention_heads={cfg.num_attention_heads} not "
            f"divisible by tp={tp}")
    if cfg.intermediate_size % tp:
        problems.append(
            f"intermediate_size={cfg.intermediate_size} not divisible "
            f"by tp={tp}")
    if n_blocks is not None and seq > 1 and n_blocks % seq:
        problems.append(
            f"n_blocks={n_blocks} not divisible by seq={seq} (the pool "
            f"page axis shards over seq)")
    if problems:
        raise ValueError("invalid mesh config: " + "; ".join(problems))


def stacked_weight_specs(names, axis=TP_AXIS):
    """PartitionSpec per stacked-parameter name (Megatron column/row
    table above; anything unlisted — norms, router — replicates)."""
    specs = {}
    for n in names:
        if n in _COL_LAST:
            specs[n] = P(None, None, axis)
        elif n in _COL_BIAS:
            specs[n] = P(None, axis)
        elif n in _ROW_AXIS1:
            specs[n] = P(None, axis, None)
        elif n in _EXPERT_COL:
            specs[n] = P(None, None, None, axis)
        elif n in _EXPERT_ROW:
            specs[n] = P(None, None, axis, None)
        else:
            specs[n] = P()
    return specs


def quant_scale_specs(scales, axis=TP_AXIS):
    """Specs for the weight-only int8 scales (``quantize_weights_int8``
    keeps one scale per OUTPUT channel, amax over the contraction axis
    with keepdims): column-parallel weights shard their scale's output
    axis alongside the weight; row-parallel weights keep per-d scales,
    which replicate. ``lm_head`` replicates with its weight."""
    specs = {}
    for n, v in scales.items():
        if n in _COL_LAST:
            specs[n] = P(None, None, axis)
        elif n in _EXPERT_COL:
            specs[n] = P(None, None, None, axis)
        else:
            specs[n] = P()
    return specs


def same_pool_placement(mesh_a, mesh_b) -> bool:
    """True when two engines' pools share one device placement, so a
    cross-pool page copy can ride ONE fused gather/scatter launch with
    both pools as live operands (r19 KV transplant). Unsharded engines
    (mesh=None on both sides) qualify — their pools sit on the same
    default device — as do engines built over the SAME mesh devices.
    Fleet workers on disjoint submeshes do NOT: their copy bounces
    through host memory, the in-process stand-in for the multi-host
    ICI/RDMA hop."""
    if mesh_a is None and mesh_b is None:
        return True
    if mesh_a is None or mesh_b is None:
        return False
    return tuple(mesh_a.devices.flat) == tuple(mesh_b.devices.flat)


def pool_specs(n_pool, axis=TP_AXIS, seq_axis=None):
    """Specs for the paged-program pool tail: kp/vp
    ``[L, N, kvh, bs, hd]`` shard their kv-head axis over ``axis`` and
    — when ``seq_axis`` is given — their page axis over ``seq_axis``;
    the int8 page scales ``[L, N, kvh]`` shard alongside (a page's
    scale lives with its codes — no cross-device scale lookup on the
    write path). ``seq_axis=None`` yields the exact r15 specs
    (``P(None, None, ...)`` — an axis entry of None IS unsharded)."""
    kv = P(None, seq_axis, axis, None, None)
    if n_pool == 4:
        sc = P(None, seq_axis, axis)
        return (kv, kv, sc, sc)
    return (kv, kv)

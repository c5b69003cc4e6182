"""Mixture-of-Experts with expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py:263 MoELayer,
MoEScatter:99/MoEGather:149 PyLayers, gates in moe/gate/, native all2all
dispatch global_scatter_op.cc/global_gather_op.cc).

TPU-native: capacity-bucketed dense dispatch — tokens are combined into
[experts, capacity, d] via one-hot matmuls (MXU-friendly, no dynamic
shapes), experts run batched, and under an 'ep' mesh axis the expert dim is
sharded so XLA inserts the all-to-all the reference issued manually."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import defop
from ...core.tensor import Tensor
from ... import nn
from .mp_layers import shard_hint

__all__ = ["MoELayer", "NaiveGate", "GShardGate", "SwitchGate",
           "moe_dispatch_combine", "moe_route_held", "moe_held_ffn"]


class NaiveGate(nn.Layer):
    """reference moe/gate/naive_gate.py: linear router, top-k."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__()
        self.gate = nn.Linear(d_model, num_expert * world_size)
        self.top_k = topk

    def forward(self, x):
        return self.gate(x)


class GShardGate(NaiveGate):
    """reference moe/gate/gshard_gate.py: GShard routing — train/eval
    capacity factors and RANDOM second-expert routing (the 2nd choice is
    kept with probability min(1, 2*g2), so weak second choices don't burn
    capacity). The me*ce aux loss is computed in moe_route and surfaced
    as MoELayer.l_aux."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), group=None):
        super().__init__(d_model, num_expert, world_size, topk)
        self.capacity = capacity

    def second_expert_drop(self, logits, training=True):
        """[N] bool: True where the 2nd choice should be DROPPED."""
        if self.top_k < 2 or not training:
            return None
        probs = jax.nn.softmax(
            jnp.asarray(logits).astype(jnp.float32), axis=-1)
        topv, _ = jax.lax.top_k(probs, 2)
        from ...ops import random as _random
        u = jax.random.uniform(_random.next_key(), (probs.shape[0],))
        return u >= jnp.minimum(1.0, 2.0 * topv[:, 1])


class SwitchGate(NaiveGate):
    """reference moe/gate/switch_gate.py: top-1 routing with train-time
    multiplicative jitter on the router logits (Switch Transformer:
    uniform noise in [1-eps, 1+eps] decorrelates routing)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4), group=None):
        super().__init__(d_model, num_expert, world_size, topk)
        self.switch_eps = switch_eps
        self.capacity = capacity

    def forward(self, x):
        logits = self.gate(x)
        if self.training and self.switch_eps:
            from ...core.tensor import Tensor
            from ...ops import random as _random
            noise = jax.random.uniform(
                _random.next_key(), jnp.asarray(logits._value).shape,
                minval=1.0 - self.switch_eps, maxval=1.0 + self.switch_eps)
            logits = logits * Tensor(noise, stop_gradient=True)
        return logits


def moe_slots(logits, num_experts, capacity, top_k, drop2_mask=None):
    """Slot metadata only — top_k on RAW logits (softmax is monotonic, so
    indices match) to keep the eager pre-pass cheap. Returns slot [N, k]
    int: flat position in the [E*C] buffer, E*C meaning 'dropped'.
    ``drop2_mask`` [N] bool: GShard random routing — the 2ND choice (and
    only it: gshard_gate.py applies the min(1, 2*g2) keep test to the
    second expert, lower-ranked choices route normally) is force-dropped
    (and doesn't consume capacity) where True."""
    _, topi = jax.lax.top_k(logits, top_k)
    n = logits.shape[0]
    flat_e = topi.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    if drop2_mask is not None and top_k >= 2:
        forced = jnp.zeros((n, top_k), bool).at[:, 1].set(
            drop2_mask).reshape(-1)
        onehot = onehot * (~forced[:, None]).astype(jnp.int32)
    else:
        forced = None
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_expert = jnp.take_along_axis(
        pos, flat_e[:, None], axis=1)[:, 0].reshape(n, top_k)
    keep = pos_in_expert < capacity
    if forced is not None:
        keep = jnp.logical_and(keep, ~forced.reshape(n, top_k))
    return jnp.where(keep, topi * capacity + pos_in_expert,
                     num_experts * capacity)


def moe_route(logits, num_experts, capacity, top_k):
    """Routing decisions on raw arrays: top-k + capacity, sort-free
    metadata. Returns (topi [N,k] int, gates [N,k] f32 normalized over
    kept slots, slot [N,k] int flat position in the [E*C] buffer with C
    meaning 'dropped', aux_loss scalar)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)                  # [N, k]
    n = probs.shape[0]
    # arrival-order position of each (token, choice) within its expert:
    # for the flattened [N*k] routing stream (token-major so earlier
    # tokens win capacity, matching the reference's priority), count
    # prior assignments to the same expert with a cumsum over one-hots
    flat_e = topi.reshape(-1)                                  # [N*k]
    onehot = jax.nn.one_hot(flat_e, num_experts,
                           dtype=jnp.int32)                  # [N*k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot                  # prior count
    pos_in_expert = jnp.take_along_axis(
        pos, flat_e[:, None], axis=1)[:, 0].reshape(n, top_k)  # [N, k]
    keep = pos_in_expert < capacity
    gates = jnp.where(keep, topv, 0.0)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    slot = jnp.where(keep, topi * capacity + pos_in_expert,
                     num_experts * capacity)                   # drop slot
    # GShard aux loss: mean_prob * mean_assignment per expert
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi, num_experts, dtype=jnp.float32).sum(1).mean(0)
    aux = (me * ce).sum() * num_experts
    return topi, gates, slot, aux


def moe_route_dropless(logits, num_experts, top_k):
    """Dropless routing (no capacity truncation): every (token, choice)
    is served. Returns (topi [N,k], gates [N,k] normalized over the full
    top-k, order [N*k] expert-sorted permutation, group_sizes [E], aux).
    The reference's capacity semantics exist for fixed-size all-to-all
    buffers; on TPU lax.ragged_dot keeps shapes static with ragged
    per-expert groups instead (MegaBlocks-style dropless). The route is
    :func:`moe_route_held` holding every expert; the GShard aux loss is
    added here."""
    topi, gates, order, group_sizes, _ = moe_route_held(logits, top_k)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi, num_experts, dtype=jnp.float32).sum(1).mean(0)
    aux = (me * ce).sum() * num_experts
    return topi, gates, order, group_sizes, aux


_STREAM_TILE = 128      # rows: a short stream is whole tiles of the chip


def moe_route_held(logits, top_k, held=None, scoring="softmax", bias=None,
                   rows=None, n_group=1, topk_group=1):
    """Dropless routing for a chip that holds a SHARE of the experts
    (expert parallelism without its exchange): every token is routed
    over all ``E`` experts the router has, and the (token, choice) pairs
    that fall on the ``held = (first, count)`` experts come out
    expert-sorted at the head of the stream; the pairs of experts that
    live on other chips sort behind them, carry weight 0 and belong to
    no group, so :func:`moe_dropless_ffn`'s grouped products run over
    the held pairs only. ``held=None`` holds every expert.

    ``scoring``: ``softmax`` or ``sigmoid`` scores over the E logits
    (float32). ``bias`` [E]: a selection bias (DeepSeek-V3's
    ``e_score_correction_bias``): the top-k is taken of ``scores +
    bias``, the weights are the scores themselves, divided by their sum
    over the chosen k (all k, wherever they live). ``rows`` [N] bool:
    tokens that are none (padding, idle slots); their pairs sort behind
    too. ``n_group`` > 1: group-limited selection (DeepSeek-V3's
    ``n_group`` / ``topk_group``): the E experts lie in ``n_group``
    groups of consecutive experts, a group's score is the sum of its two
    largest ``scores + bias``, the ``topk_group`` best groups stay and
    the top-k is taken among their experts alone.

    Returns (topi [N, k] expert ids, gates [N, k] f32, 0 off the held
    share, order [N*k] the stream's permutation, group_sizes [count]
    int32, stream_rows). ``group_sizes.sum()`` pairs are computed here;
    ``(group_sizes > 0).sum()`` experts are visited.

    ``stream_rows`` (P, a Python int, from the shapes and ``held``
    alone) is how many rows of the stream a share's pairs are expected
    to fit: an even router brings ``N * k * count / E`` pairs; P is
    twice that, rounded up to whole tiles of 128 rows, at most ``N *
    k``. Because the held pairs lie at the HEAD of ``order``,
    :func:`moe_dropless_ffn` may read ``order[:r]`` alone whenever
    ``group_sizes.sum() <= r``; it relies on that place."""
    x = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(x, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(x)
    else:
        raise ValueError(f"unknown scoring {scoring!r}: softmax | sigmoid")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        with jax.named_scope("moe_group_route"):
            choice = _keep_best_groups(choice, n_group, topk_group)
    _, topi = jax.lax.top_k(choice, top_k)
    gates = jnp.take_along_axis(scores, topi, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    n_experts = logits.shape[-1]
    first, count = held if held is not None else (0, n_experts)
    local = topi - first
    mine = (local >= 0) & (local < count)
    if rows is not None:
        mine = mine & rows[:, None]
    key = jnp.where(mine, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.bincount(key, length=count + 1)[:count].astype(
        jnp.int32)
    n_pairs = key.shape[0]
    stream_rows = min(n_pairs, -(-2 * n_pairs * count
                                 // (n_experts * _STREAM_TILE)) * _STREAM_TILE)
    return topi, jnp.where(mine, gates, 0.0), order, group_sizes, stream_rows


def _keep_best_groups(choice, n_group, topk_group):
    """choice [N, E] with -inf at the experts outside each token's
    ``topk_group`` best of ``n_group`` groups of consecutive experts (a
    group's score: the sum of its two largest entries; of equal groups
    the first)."""
    n, e = choice.shape
    per = choice.reshape(n, n_group, e // n_group)
    score = jax.lax.top_k(per, 2)[0].sum(axis=-1)               # [N, groups]
    _, best = jax.lax.top_k(score, topk_group)
    kept = (best[:, :, None] == jnp.arange(n_group)).any(axis=1)
    return jnp.where(kept[:, :, None], per, -jnp.inf).reshape(n, e)


def moe_stream_rungs(n_pairs, stream_rows):
    """The lengths of the short streams :func:`moe_dropless_ffn` offers
    a call of ``n_pairs`` pairs whose route said ``stream_rows`` (P),
    ascending; ``()`` where the whole stream is the only program: P is
    not at most half of it (every expert held: never shorter). The chip's
    grouped kernel tiles a stream's rows by 512 where 512 divides it,
    else by 256, else by 128, and computes a whole tile for every expert
    it visits (PERF.md, Findings PR 39 and 46), so a rung is an ODD
    number of tiles of 128 rows: P / 2 (what an even router brings,
    where that is whole tiles) and P, each a tile longer where its tiles
    are even: P = 128 gives (128), 256 (128, 384), 512 (384, 640)."""
    if stream_rows is None or 2 * stream_rows > n_pairs:
        return ()
    tiles = (r // _STREAM_TILE for r in (stream_rows // 2, stream_rows)
             if r % _STREAM_TILE == 0)
    return tuple((t + 1 - t % 2) * _STREAM_TILE for t in tiles)


def moe_stream_rows(group_sizes, n_pairs, stream_rows=None):
    """How many rows of the stream :func:`moe_dropless_ffn` runs its
    products over: the first of :func:`moe_stream_rungs` that holds this
    call's pairs (traced), else the whole stream's ``n_pairs``; the
    Python int ``n_pairs`` where no short stream is offered."""
    rows = n_pairs
    for rung in reversed(moe_stream_rungs(n_pairs, stream_rows)):
        rows = jnp.where(group_sizes.sum() <= rung, rung, rows)
    return rows


def moe_dropless_ffn(tokens, topi, gates, order, group_sizes,
                     we_gate, we_up, we_down, precision=None,
                     stream_rows=None):
    """SwiGLU expert FFN over the expert-sorted ragged stream: three
    lax.ragged_dot grouped GEMMs, then unsort + gate-combine. tokens
    [N, d]; we_* [E, d, f]/[E, f, d]; returns [N, d]. Rows of the stream
    past the groups' total (:func:`moe_route_held`: pairs of experts
    this chip does not hold) belong to no expert; whatever the grouped
    product leaves there is dropped. ``precision``: the grouped
    products' (None: the process's default, which at ``"high"`` makes
    the chip's compiler spell a grouped product out as a dense one over
    every group; ``Precision.DEFAULT`` keeps the grouped kernel, which
    reads the experts that have rows).

    ``stream_rows``: the P that :func:`moe_route_held` returned with
    ``order`` (never a caller's choice). Where P is at most half of the
    stream's ``N * k`` rows (a chip that holds a share of the experts),
    the stream, the three products, the mask and the combine are built
    over the HEAD of the expert-sorted order, where the held pairs lie:
    over the first of :func:`moe_stream_rungs` (about P / 2, what an
    even router brings, and P) that holds this call's pairs; a call
    whose pairs outgrow the last takes the whole stream, so nothing is
    dropped (:func:`moe_stream_rows` says which ran). The grouped
    kernel computes a whole tile of rows for every expert it visits, so
    a stream no longer than its pairs need, of a length that keeps the
    tile at 128 rows, is what keeps it at the experts' bytes. Elsewhere
    (``None``, every expert held) the whole stream is the only program
    traced."""
    n, d = tokens.shape
    k = topi.shape[1]
    dt = we_gate.dtype

    def products(stream):
        gate = jax.nn.silu(jax.lax.ragged_dot(stream, we_gate, group_sizes,
                                              precision=precision))
        up = jax.lax.ragged_dot(stream, we_up, group_sizes,
                                precision=precision)
        out = jax.lax.ragged_dot(gate * up, we_down, group_sizes,
                                 precision=precision)
        grouped = jnp.arange(out.shape[0]) < group_sizes.sum()
        return jnp.where(grouped[:, None], out, 0)

    def full():
        stream = jnp.repeat(tokens, k, axis=0) if k > 1 else tokens
        stream = jnp.take(stream, order, axis=0)              # [N*k, d]
        out_sorted = products(stream.astype(dt))
        unsorted = jnp.zeros_like(out_sorted).at[order].set(out_sorted)
        picked = unsorted.reshape(n, k, d)
        return jnp.sum(picked * gates[..., None].astype(picked.dtype),
                       axis=1)

    def head(rows):
        pairs = order[:rows]                        # each n * k + choice
        token = pairs // k
        out = products(jnp.take(tokens, token, axis=0).astype(dt))
        weight = jnp.take(gates.reshape(-1), pairs)     # 0 past the groups
        out = (out.astype(jnp.float32) * weight[:, None]).astype(dt)
        # a token's pairs added up in float32: [N, rows] ones at its pairs
        at = (token[None, :] == jnp.arange(n)[:, None]).astype(dt)
        return jnp.dot(at, out, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32).astype(dt)

    rungs = moe_stream_rungs(n * k, stream_rows)
    if not rungs:
        return full()
    rung = sum((group_sizes.sum() > r).astype(jnp.int32) for r in rungs)
    return jax.lax.switch(
        rung, [functools.partial(head, r) for r in rungs] + [full])


def moe_held_ffn(tokens, router, bias, experts, layer, rows, counts, *,
                 top_k, held, scoring, n_group=1, topk_group=1,
                 gate_scale=None):
    """The routed half of an expert layer on a chip that holds a SHARE
    of the router's experts: tokens [N, d] (normed) are routed over all
    of ``router``'s [d, E] outputs in float32 (:func:`moe_route_held`
    takes the other arguments) and the held experts' part of the sum is
    computed over the head of the stream (:func:`moe_dropless_ffn`, scope
    ``moe_expert_ffn``), the weights times ``gate_scale`` where a family
    scales them. ``experts`` (we_gate, we_up, we_down): ONE stack of all
    expert layers' held experts ``[layers * count, ...]``, this
    ``layer``'s (data) named by their place in it. ``counts`` int32 gains
    (pairs computed, held experts visited, 1 if the products took the
    whole stream, with ``n_group`` > 1 the groups that hold a chosen
    expert of some real token, last the stream's rows the products ran
    over). Returns ([N, d] in the experts' dtype, counts)."""
    logits = jnp.dot(tokens.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    topi, gates, order, sizes, stream_rows = moe_route_held(
        logits, top_k, held, scoring=scoring, bias=bias, rows=rows,
        n_group=n_group, topk_group=topk_group)
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((experts[0].shape[0],), jnp.int32), sizes,
        (layer * held[1],))
    with jax.named_scope("moe_expert_ffn"):
        if gate_scale is not None:
            gates = gates * gate_scale
        out = moe_dropless_ffn(tokens, topi, gates, order, groups, *experts,
                               precision=jax.lax.Precision.DEFAULT,
                               stream_rows=stream_rows)
    ran = moe_stream_rows(sizes, order.shape[0], stream_rows)
    gained = [sizes.sum(), (sizes > 0).sum(), ran == order.shape[0]]
    if n_group > 1:
        group = topi // (router.shape[-1] // n_group)
        seen = (group[:, :, None] == jnp.arange(n_group)) & rows[:, None, None]
        gained.append(seen.any(axis=(0, 1)).sum())
    return out, counts + jnp.stack(
        [jnp.asarray(g, jnp.int32) for g in (*gained, ran)])


def moe_permute(x, slot, num_experts, capacity):
    """Scatter tokens into the [E*C(+1 drop row), d] expert buffer —
    O(N·k·d) scatter instead of the dense [N, E, C] one-hot matmul
    (VERDICT weak #7: the dense combine is a 0.5G-element intermediate at
    Mixtral scale)."""
    n, d = x.shape
    k = slot.shape[1]
    buf = jnp.zeros((num_experts * capacity + 1, d), x.dtype)
    flat_slot = slot.reshape(-1)
    tokens = jnp.repeat(x, k, axis=0) if k > 1 else x
    buf = buf.at[flat_slot].add(tokens)                 # dup sends add once
    return buf[:num_experts * capacity].reshape(num_experts, capacity, d)


def moe_unpermute(expert_out, slot, gates, n_tokens):
    """Gather each (token, choice)'s expert output and gate-combine:
    [E, C, d] -> [N, d]."""
    e, c, d = expert_out.shape
    flat = jnp.concatenate(
        [expert_out.reshape(e * c, d),
         jnp.zeros((1, d), expert_out.dtype)])           # drop row reads 0
    picked = jnp.take(flat, slot.reshape(-1), axis=0)    # [N*k, d]
    k = slot.shape[1]
    picked = picked.reshape(n_tokens, k, d)
    return jnp.sum(picked * gates[..., None].astype(picked.dtype), axis=1)


@defop("moe_dispatch")
def _dispatch(x, logits, slot, num_experts, capacity, top_k):
    """tokens [N, d], logits [N, E], slot metadata -> (expert_inputs
    [E, C, d], gates [N, k], aux loss). Sort/scatter dispatch (no
    [N, E, C] dense intermediate). ``slot`` is int routing metadata passed
    as a closed-over raw array — integer outputs/primals would poison the
    vjp with float0 cotangents."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    keep = slot < num_experts * capacity
    gates = jnp.where(keep, topv, 0.0)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    expert_inputs = moe_permute(x, slot, num_experts, capacity)
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi, num_experts, dtype=jnp.float32).sum(1).mean(0)
    aux = (me * ce).sum() * num_experts
    return expert_inputs, gates.astype(x.dtype), aux.astype(x.dtype)


@defop("moe_combine")
def _combine(expert_outputs, gates, slot):
    n = slot.shape[0]
    return moe_unpermute(expert_outputs, slot, gates, n)


def moe_dispatch_combine(x, logits, num_experts, capacity, top_k,
                         drop2_mask=None):
    """Returns (expert_in, gates, slot_raw, aux). slot is a raw int array
    (routing metadata, not a differentiable Tensor)."""
    lv = logits._value if isinstance(logits, Tensor) else jnp.asarray(logits)
    slot = moe_slots(lv, num_experts, capacity, top_k,
                     drop2_mask=drop2_mask)
    expert_in, gates, aux = _dispatch(
        x, logits, slot=slot, num_experts=num_experts, capacity=capacity,
        top_k=top_k)
    return expert_in, gates, slot, aux


class MoELayer(nn.Layer):
    """reference moe_layer.py:263. gate → dispatch (all2all over 'ep') →
    expert FFN (batched) → gather.

    ``experts`` is a list of expert Layers with identical structure; their
    parameters are stacked into [E, ...] buffers so one batched einsum runs
    all experts (vmap-style), and the E dim shards over the 'ep' axis."""

    def __init__(self, d_model=None, experts=None, gate=None, moe_group=None,
                 mp_group=None, recompute_interval=0, top_k=2,
                 capacity_factor=None, **kwargs):
        super().__init__()
        if isinstance(gate, dict):
            gate_type = gate.get("type", "gshard")
            cls = {"naive": NaiveGate, "gshard": GShardGate,
                   "switch": SwitchGate}[gate_type]
            gate = cls(d_model, len(experts), topk=gate.get("top_k", top_k))
        self.gate = gate or NaiveGate(d_model, len(experts), topk=top_k)
        self.experts = nn.LayerList(experts)
        self.num_experts = len(experts)
        self.top_k = getattr(self.gate, "top_k", top_k)
        self.capacity_factor = capacity_factor

    def forward(self, x):
        orig_shape = x.shape
        from ...ops.manipulation import reshape
        d = orig_shape[-1]
        x2 = reshape(x, [-1, d])
        n_tokens = x2.shape[0]
        # explicit capacity_factor wins; else the gate's train/eval
        # capacity pair (GShard/Switch); else the 1.25 default
        factor = self.capacity_factor
        if factor is None:
            if hasattr(self.gate, "capacity"):
                factor = self.gate.capacity[0 if self.training else 1]
            else:
                factor = 1.25
        capacity = max(1, int(factor * n_tokens
                              * self.top_k / self.num_experts))
        logits = self.gate(x2)
        drop2 = None
        if isinstance(self.gate, GShardGate):
            drop2 = self.gate.second_expert_drop(
                logits._value, training=self.training)
        expert_in, gates, slot, aux = moe_dispatch_combine(
            x2, logits, self.num_experts, capacity, self.top_k,
            drop2_mask=drop2)
        # shard expert dim over 'ep' (all-to-all inserted by GSPMD)
        expert_in = shard_hint(expert_in, "ep", None, None)
        outs = []
        for i, expert in enumerate(self.experts):
            outs.append(expert(expert_in[i]))
        from ...ops.manipulation import stack
        expert_out = stack(outs, axis=0)
        expert_out = shard_hint(expert_out, "ep", None, None)
        y = _combine(expert_out, gates, slot=slot)
        self.l_aux = aux
        return reshape(y, orig_shape)

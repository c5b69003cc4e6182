"""Granite 4.0-H family (``model_type: granitemoehybrid`` with no routed
experts): a stack whose ``layer_types`` mix Mamba-2 state-space mixers
with a few attention mixers that carry no positional embedding, every
layer followed by one shared SwiGLU, residuals and logits scaled by the
config's multipliers. Serving only, through the paged ``DecodeEngine``.

What a slot of the engine holds for this family is of two kinds: pages
of keys and values for the attention layers, in the engine's block pool,
and for every Mamba-2 layer a fixed-size recurrent state
``[heads, head_dim, state]`` in **float32** (it is carried over every
token of a request; stored as ``kernels/ssm_update.py`` lays it out)
plus the last ``d_conv - 1`` inputs of the causal convolution in the
model's dtype. Both state arrays are stacked over the
Mamba layers and the engine's slots (``[Lm, slots, ...]``), ride behind
the pools through the two paged programs, and are donated and updated in
place. The KV pool and the convolution window are the model's dtype.

The stack is driven by ``layer_types`` as data: runs of like layers are
scanned, each run indexing the stacked weights where they lie.

Attention heads narrower than a lane tile (64 here) are stored ``pack``
kv heads to a pool "head" of 128 lanes, so that the pool has the shape
the paged decode kernel serves; a query is laid into its own head's lanes
and zero elsewhere, which leaves every score and output as it was.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..kernels.paged_attention import paged_decode_attention
from ..kernels.ssm_update import lane_pack, pack_state, ssm_decode_update
from .llama import _attention_keymask, _attention_prefix_span, _rms
from .paged_stack import (PagedPrograms, _row_pages, _token_insert,
                          block_window, greedy_chunk, run_scans, scan_runs,
                          walk_blocks)

__all__ = ["GraniteHybridConfig", "GraniteHybridForCausalLM",
           "GRANITE_PRESETS"]

_HI = jax.lax.Precision.HIGHEST


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192       # shared_intermediate_size
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = ()             # "mamba" | "attention" per layer
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in ("mamba", "attention") for t in self.layer_types):
            raise ValueError(
                f"layer_types must name 'mamba' or 'attention' for each of "
                f"the {self.num_hidden_layers} layers, got "
                f"{self.layer_types!r}")
        if self.mamba_n_groups != 1 or not self.tie_word_embeddings:
            raise ValueError(
                "GraniteHybrid supports mamba_n_groups=1 and tied "
                "embeddings (the published granite-4.0-h configurations)")
        if self.d_inner != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand * hidden_size = {self.d_inner} is not "
                f"mamba_n_heads * mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def n_mamba(self):
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def n_attention(self):
        return self.num_hidden_layers - self.n_mamba

    def runs(self):
        """The stack as runs of like layers: (kind, first layer, first
        index within its kind, length)."""
        out, seen = [], {"mamba": 0, "attention": 0}
        for l, kind in enumerate(self.layer_types):
            if out and out[-1][0] == kind:
                out[-1][3] += 1
            else:
                out.append([kind, l, seen[kind], 1])
            seen[kind] += 1
        return [tuple(r) for r in out]


def _period(n, every, at):
    return tuple("attention" if l % every == at else "mamba"
                 for l in range(n))


GRANITE_PRESETS = {
    "granite-4.0-h-micro": dict(layer_types=_period(40, 10, 5)),
    "debug": dict(vocab_size=128, hidden_size=128, intermediate_size=256,
                  num_hidden_layers=5, num_attention_heads=4,
                  num_key_value_heads=2,
                  layer_types=("mamba", "mamba", "attention", "mamba",
                               "attention"),
                  mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
                  mamba_chunk_size=8),
}

_SHARED = ("input_ln", "post_ln", "w_gate", "w_up", "w_down")
_MAMBA = ("in_proj", "dt_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "ssm_norm", "out_proj")
_ATTN = ("wq", "wk", "wv", "wo")
_OWN = {"mamba": _MAMBA, "attention": _ATTN}


def _layer_params(w, kind, l, i):
    """Layer ``l``'s leaves, index ``i`` within its ``kind``, taken from
    the stacks where they lie (``l`` and ``i`` are data)."""
    lp = {n: w[n][l] for n in _SHARED}
    lp.update({n: w[n][i] for n in _OWN[kind]})
    return lp


def _mlp(cfg, lp, x):
    y = _rms(x, lp["post_ln"], cfg.rms_norm_eps)
    gate = jax.nn.silu(y @ lp["w_gate"])
    return x + cfg.residual_multiplier * ((gate * (y @ lp["w_up"]))
                                          @ lp["w_down"])


def _kv_pack(kvh, hd):
    """kv heads stored side by side in one pool head of up to 128 lanes."""
    pack = 1
    while 2 * pack * hd <= 128 and kvh % (2 * pack) == 0:
        pack *= 2
    return pack


def _qkv(cfg, lp, h):
    """Bias-free projections of h [..., d] to heads of ``head_dim``. No
    positional embedding. The caller lays the whole softmax scale
    (``attention_multiplier``) on the query, against the 1/sqrt(width)
    the attention read it uses divides by."""
    hd = cfg.head_dim
    q = (h @ lp["wq"]).reshape(*h.shape[:-1], -1, hd)
    k = (h @ lp["wk"]).reshape(*h.shape[:-1], -1, hd)
    v = (h @ lp["wv"]).reshape(*h.shape[:-1], -1, hd)
    return q, k, v


def _pack_q(q, kvh, pack):
    """q [b, h, hd] -> [b, kvh/pack, pack*g, pack*hd]: each query in the
    lanes of its own kv head, zeros in its neighbours'."""
    b, h, hd = q.shape
    g = h // kvh
    qg = q.reshape(b, kvh // pack, pack, g, hd)
    eye = jnp.eye(pack, dtype=q.dtype)
    out = qg[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
    return out.reshape(b, kvh // pack, pack * g, pack * hd)


def _unpack_o(o, pack):
    """The inverse read of :func:`_pack_q` on the attention's output
    [b, kvh/pack, pack*g, pack*hd] -> [b, h*hd]."""
    b, j, pg, phd = o.shape
    g, hd = pg // pack, phd // pack
    o = o.reshape(b, j, pack, g, pack, hd)
    eye = jnp.eye(pack, dtype=o.dtype)
    out = (o * eye[None, None, :, None, :, None]).sum(axis=4)
    return out.reshape(b, j * pack * g * hd)


def _conv_silu(full, lp, n_out):
    """Depthwise causal convolution over ``full`` [.., n_out + K - 1, C]
    (the window then the new inputs) with its bias, then SiLU; float32."""
    w = lp["conv_w"].astype(jnp.float32)
    acc = lp["conv_b"].astype(jnp.float32)
    for k in range(w.shape[0]):
        acc = acc + jax.lax.slice_in_dim(
            full, k, k + n_out, axis=-2).astype(jnp.float32) * w[k]
    return jax.nn.silu(acc)


def _gated_out(cfg, lp, y, xs, z, dtype):
    """D skip, gate, the norm over all of d_inner, the output matmul."""
    y = y + lp["D"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
    y = _rms(y, lp["ssm_norm"].astype(jnp.float32), cfg.rms_norm_eps)
    return y.astype(dtype) @ lp["out_proj"]


def _in_proj(cfg, lp, h):
    """(z, xBC, dt) of h [.., d]. The published ``in_proj`` is held as
    two leaves, its z | xBC columns and its dt columns (``dt_proj``):
    the first is then a whole number of lane tiles wide, and the chip's
    compiler takes the stack as it lies (with the 64 dt columns behind
    it, it copied all of it at every launch)."""
    zx = h @ lp["in_proj"]
    return (zx[..., :cfg.d_inner], zx[..., cfg.d_inner:],
            h @ lp["dt_proj"])


def _split_xbc(cfg, xbc):
    di, ds = cfg.d_inner, cfg.mamba_d_state
    xs = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads,
                               cfg.mamba_d_head)
    return xs, xbc[..., di:di + ds], xbc[..., di + ds:]


def _ssd_chunk(state, xs, dt, a_neg, bm, cm):
    """One chunk of the recurrence ``S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t (x) B_t``, ``y_t = S_t C_t`` as matmuls: state [h, p, n]
    float32 entering the chunk; xs [q, h, p], dt [q, h], bm/cm [q, n]
    float32. Returns (state leaving the chunk, y [q, h, p])."""
    q = xs.shape[0]
    cum = jnp.cumsum(dt * a_neg, axis=0)                 # [q, h], <= 0
    cum_h = cum.T                                        # [h, q]
    seg = cum_h[:, :, None] - cum_h[:, None, :]          # [h, i, j]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal[None], seg, -jnp.inf))
    cb = jnp.einsum("in,jn->ij", cm, bm, precision=_HI)
    m = cb[None] * decay * dt.T[:, None, :]              # [h, i, j]
    y = jnp.einsum("hij,jhp->ihp", m, xs, precision=_HI)
    y = y + jnp.einsum("in,hpn->ihp", cm, state,
                       precision=_HI) * jnp.exp(cum)[:, :, None]
    to_end = jnp.exp(cum[-1][None, :] - cum) * dt        # [q, h]
    state = jnp.exp(cum[-1])[:, None, None] * state + jnp.einsum(
        "jhp,jn->hpn", to_end[:, :, None] * xs, bm, precision=_HI)
    return state, y


def _mamba_seq(cfg, lp, h, state, window, valid):
    """The Mamba-2 mixer over one sequence's tokens h [s, d] (``s`` a
    multiple of the chunk), entered with ``state`` [h, p, n] float32 and
    the convolution's ``window`` [K-1, C]; ``valid`` [s] marks real
    tokens. A token that is not real enters the convolution as zero and
    takes a step of dt = 0, so it leaves state and window as they were.
    Returns (mixer output [s, d], state, window)."""
    s = h.shape[0]
    q = min(cfg.mamba_chunk_size, s)
    z, xbc, dt = _in_proj(cfg, lp, h)
    xbc = jnp.where(valid[:, None], xbc, 0)
    full = jnp.concatenate([window, xbc], axis=0)
    window = full[s:]
    xs, bm, cm = _split_xbc(cfg, _conv_silu(full, lp, s))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    dt = jnp.where(valid[:, None], dt, 0.0)
    a_neg = -jnp.exp(lp["A_log"].astype(jnp.float32))

    split = lambda a: a.reshape(s // q, q, *a.shape[1:])
    state, y = jax.lax.scan(
        lambda st, c: _ssd_chunk(st, c[0], c[1], a_neg, c[2], c[3]),
        state, (split(xs), split(dt), split(bm), split(cm)))
    y = y.reshape(s, *y.shape[2:])
    return _gated_out(cfg, lp, y, xs, z, h.dtype), state, window


def _mamba_decode(cfg, lp, x, m, ssm, conv, live):
    """The mixer for one token per slot: x [b, d] -> (output, ssm, conv).
    The states of the ``live`` slots alone are read and written."""
    h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
    z, xbc, dt = _in_proj(cfg, lp, h)
    full = jnp.concatenate([conv[m], xbc[:, None].astype(conv.dtype)],
                           axis=1)                      # [b, K, C]
    conv = jax.lax.dynamic_update_index_in_dim(conv, full[:, 1:], m, 0)
    xs, bm, cm = _split_xbc(cfg, _conv_silu(full, lp, 1)[:, 0])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(lp["A_log"].astype(jnp.float32)))
    ssm, y = ssm_decode_update(ssm, m, xs, dt, a, bm, cm, live)
    return _gated_out(cfg, lp, y, xs, z, x.dtype), ssm, conv


def _attention_decode(cfg, lp, x, a, kp, vp, tables, lens):
    """The attention mixer for one token per slot against layer ``a`` of
    the paged pools (heads packed :func:`_kv_pack` to a pool head)."""
    b = x.shape[0]
    kvh, hd = cfg.num_key_value_heads, cfg.head_dim
    pack = _kv_pack(kvh, hd)
    bs = kp.shape[-2]
    h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
    q, k, v = _qkv(cfg, lp, h)
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    off = lens % bs
    kp = _token_insert(kp, a, page, off, k.reshape(b, kvh // pack, -1))
    vp = _token_insert(vp, a, page, off, v.reshape(b, kvh // pack, -1))
    # the read divides by sqrt(pack * hd)
    scale = cfg.attention_multiplier * (pack * hd) ** 0.5
    qg = _pack_q(q.astype(jnp.float32) * scale, kvh, pack)
    o = paged_decode_attention(qg, kp, vp, tables, lens + 1, a)
    return _unpack_o(o, pack).astype(x.dtype) @ lp["wo"], kp, vp


def _decode_step(cfg, w, embed, final_norm, tok, tables, lens, pool,
                 live):
    """One token per slot through the whole stack: tok [b] ->
    (float32 logits [b, V], pool); pool = (kp, vp, ssm, conv)."""
    x = jnp.take(embed, tok, axis=0) * jnp.asarray(
        cfg.embedding_multiplier, embed.dtype)
    rm = cfg.residual_multiplier

    def layer(kind, l0, i0, carry, j):
        x, (kp, vp, ssm, conv) = carry
        lp = _layer_params(w, kind, l0 + j, i0 + j)
        if kind == "mamba":
            out, ssm, conv = _mamba_decode(cfg, lp, x, i0 + j, ssm, conv,
                                           live)
        else:
            out, kp, vp = _attention_decode(cfg, lp, x, i0 + j, kp, vp,
                                            tables, lens)
        return (_mlp(cfg, lp, x + rm * out), (kp, vp, ssm, conv)), None

    x, pool = scan_runs(cfg.runs(), layer, (x, tuple(pool)))
    return _logits(cfg, x, embed, final_norm), pool


def _logits(cfg, x, embed, final_norm):
    """Float32 logits of x [.., d] through the tied head, read from the
    embedding as it lies."""
    x = _rms(x, final_norm, cfg.rms_norm_eps)
    logits = jax.lax.dot_general(x, embed, (((x.ndim - 1,), (1,)), ((), ())))
    return logits.astype(jnp.float32) / cfg.logits_scaling


def _prefill(cfg, w, embed, final_norm, ids, pad_len, table_row, slot, pool,
             block):
    """The cold prefill of ONE right-aligned row (ids [1, s], pad_len
    [1]); ``paged_stack.walk_blocks`` has the walk. The family's own: a
    block's Mamba layers run the chunked recurrence with state and
    convolution window carried from block to block, its attention layers
    read the row's earlier keys from a contiguous carry; at the window's
    end keys and values are written page by page through ``table_row``,
    states and windows into ``slot``. Returns (float32 logits, pool)."""
    kp, vp, ssm, conv = pool
    window = block_window(ids, pad_len, block)
    pad = window.pad
    real = (jnp.arange(window.total) >= pad)[None]
    kvh, hd = cfg.num_key_value_heads, cfg.head_dim
    pack = _kv_pack(kvh, hd)
    rm = cfg.residual_multiplier
    dtype = embed.dtype
    # attention reads divide by sqrt(hd)
    q_scale = jnp.asarray(cfg.attention_multiplier * hd ** 0.5, dtype)

    def init():
        kv0 = jnp.zeros((cfg.n_attention, window.total, kvh, hd), dtype)
        return (kv0, kv0,
                jnp.zeros((cfg.n_mamba, cfg.mamba_n_heads, cfg.mamba_d_head,
                           cfg.mamba_d_state), ssm.dtype),
                jnp.zeros((conv.shape[0],) + conv.shape[2:], conv.dtype))

    def run_layers(x, state, blk):
        kc, vc, sc, cc = state
        x = x * jnp.asarray(cfg.embedding_multiplier, dtype)

        def mamba_layer(_, l0, i0, x, j, st, win):
            lp = _layer_params(w, "mamba", l0 + j, i0 + j)
            h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
            out, st, win = _mamba_seq(cfg, lp, h, st, win, blk.rows)
            return _mlp(cfg, lp, x + rm * out), (st, win)

        def attention_layer(_, l0, i0, carry, j):
            x, kc, vc = carry
            a = i0 + j
            lp = _layer_params(w, "attention", l0 + j, a)
            h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
            q, k, v = _qkv(cfg, lp, h)
            o = _attention_prefix_span(
                (q * q_scale)[None], k[None], v[None], blk.rows[None],
                kc[a][None], vc[a][None], real,
                (blk.first, blk.i, window.block))
            kc = jax.lax.dynamic_update_slice(
                kc, k[None], (a, blk.start, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, v[None], (a, blk.start, 0, 0))
            out = o.reshape(window.block, -1) @ lp["wo"]
            return (_mlp(cfg, lp, x + rm * out), kc, vc), None

        kept = []
        for (kind, _, i0, n), scan in run_scans(cfg.runs()):
            if kind == "mamba":
                x, states = scan(mamba_layer, x, sc[i0:i0 + n],
                                 cc[i0:i0 + n])
                kept.append(states)
            else:
                (x, kc, vc), _ = scan(attention_layer, (x, kc, vc))
        sc, cc = (jnp.concatenate(states) for states in zip(*kept))
        return x, (kc, vc, sc, cc)

    (kc, vc, sc, cc), last = walk_blocks(window, embed, init, run_layers,
                                         positions=False)
    logits = _logits(cfg, last, embed, final_norm)
    mb, bs = table_row.shape[0], kp.shape[-2]
    kp = kp.at[:, table_row].set(_row_pages(kc, pad, mb, bs, pack))
    vp = vp.at[:, table_row].set(_row_pages(vc, pad, mb, bs, pack))
    sc = pack_state(sc, cfg.mamba_n_heads // ssm.shape[2])
    ssm = jax.lax.dynamic_update_slice(ssm, sc[:, None], (0, slot, 0, 0, 0))
    conv = jax.lax.dynamic_update_slice(conv, cc[:, None], (0, slot, 0, 0))
    return logits, (kp, vp, ssm, conv)


def _forward(cfg, w, embed, final_norm, ids):
    """Float32 logits [s, V] of one sequence ids [s], no cache: the
    chunked recurrence over the whole sequence (padded on the right to a
    multiple of the chunk), full causal attention."""
    s = ids.shape[0]
    q = cfg.mamba_chunk_size
    ids = jnp.pad(ids, (0, -s % q))
    valid = jnp.arange(ids.shape[0]) < s
    dtype = embed.dtype
    x = jnp.take(embed, ids, axis=0) * jnp.asarray(
        cfg.embedding_multiplier, dtype)
    rm = cfg.residual_multiplier
    q_scale = jnp.asarray(cfg.attention_multiplier * cfg.head_dim ** 0.5,
                          dtype)
    state = jnp.zeros((cfg.mamba_n_heads, cfg.mamba_d_head,
                       cfg.mamba_d_state), jnp.float32)
    window = jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_dim), dtype)

    def layer(kind, l0, i0, x, j):
        lp = _layer_params(w, kind, l0 + j, i0 + j)
        h = _rms(x, lp["input_ln"], cfg.rms_norm_eps)
        if kind == "mamba":
            out, _, _ = _mamba_seq(cfg, lp, h, state, window, valid)
        else:
            qh, k, v = _qkv(cfg, lp, h)
            o = _attention_keymask((qh * q_scale)[None], k[None], v[None],
                                   valid[None])
            out = o.reshape(x.shape[0], -1) @ lp["wo"]
        return _mlp(cfg, lp, x + rm * out), None

    x = scan_runs(cfg.runs(), layer, x)
    return _logits(cfg, x[:s], embed, final_norm)


class GraniteHybridForCausalLM(nn.Layer):
    """Stacked-parameter Granite 4.0-H: the shared leaves (norms, SwiGLU)
    stacked over all layers, the Mamba-2 and the attention leaves each
    over the layers of their kind."""

    def __init__(self, config: GraniteHybridConfig | str = "debug"):
        super().__init__()
        if isinstance(config, str):
            config = GraniteHybridConfig(**GRANITE_PRESETS[config])
        self.config = cfg = config
        d, ff = cfg.hidden_size, cfg.intermediate_size
        L, Lm, La = cfg.num_hidden_layers, cfg.n_mamba, cfg.n_attention
        h, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        nh, di, C = cfg.mamba_n_heads, cfg.d_inner, cfg.conv_dim

        def mk(name, shape, init, f32=False):
            p = self.create_parameter(shape=shape, default_initializer=init)
            if cfg.dtype != "float32" and not f32:
                p._in_place_update(p._value.astype(cfg.dtype))
            self.add_parameter(name, p)
            return p

        from ..nn import initializer as I
        mat, one = I.Normal(0.0, 0.02), I.Constant(1.0)
        self.embed_tokens = mk("embed_tokens", [cfg.vocab_size, d], mat)
        mk("input_ln", [L, d], one)
        mk("post_ln", [L, d], one)
        mk("w_gate", [L, d, ff], mat)
        mk("w_up", [L, d, ff], mat)
        mk("w_down", [L, ff, d], mat)
        mk("in_proj", [Lm, d, di + C], mat)
        mk("dt_proj", [Lm, d, nh], mat)
        mk("conv_w", [Lm, cfg.mamba_d_conv, C], I.Normal(0.0, 0.3))
        mk("conv_b", [Lm, C], I.Normal(0.0, 0.02))
        # Mamba-2's own ranges: softplus(dt_bias) about 0.01, A in 1..16,
        # D = 1; kept float32 like the state they drive
        mk("dt_bias", [Lm, nh], I.Constant(-4.6), f32=True)
        mk("A_log", [Lm, nh], I.Uniform(0.0, 2.77), f32=True)
        mk("D", [Lm, nh], one, f32=True)
        mk("ssm_norm", [Lm, di], one)
        mk("out_proj", [Lm, di, d], mat)
        mk("wq", [La, d, h * hd], mat)
        mk("wk", [La, d, kvh * hd], mat)
        mk("wv", [La, d, kvh * hd], mat)
        mk("wo", [La, h * hd, d], mat)
        self.final_norm = mk("final_norm", [d], one)

    def _stacked_names(self):
        return list(_SHARED + _MAMBA + _ATTN)

    def forward(self, input_ids):
        """Float32 logits [b, s, V]."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        w = {n: self._parameters[n]._value for n in self._stacked_names()}
        fwd = jax.vmap(lambda row: _forward(
            self.config, w, self._parameters["embed_tokens"]._value,
            self._parameters["final_norm"]._value, row))
        return Tensor(fwd(ids), stop_gradient=True)

    def paged_programs(self, chunk, prefill_block, mp_axis=None,
                       seq_axis=None, n_seq=1):
        """What ``DecodeEngine`` binds for this family."""
        cfg = self.config
        kvh, hd = cfg.num_key_value_heads, cfg.head_dim
        pack = _kv_pack(kvh, hd)
        if prefill_block % min(cfg.mamba_chunk_size, prefill_block):
            raise ValueError(
                f"the cold prefill's block of {prefill_block} rows is no "
                f"multiple of mamba_chunk_size={cfg.mamba_chunk_size}")

        def prefill_paged(stacked, embed, fnorm, lm, scales, ids, pad_len,
                          table_row, slot, *pool):
            """ids [1, s_max] right-aligned; the row's keys and values go
            into its pages, its Mamba states and convolution windows
            into ``slot`` of the state arrays, inside the program."""
            logits, pool = _prefill(cfg, stacked, embed, fnorm, ids,
                                    pad_len, table_row, slot, pool,
                                    prefill_block)
            return (jnp.argmax(logits, axis=-1), *pool)

        def decode_chunk_paged(stacked, embed, fnorm, lm, scales, tok,
                               tables, lens, *pool):
            """One chunk; a slot with ``lens == 0`` holds no row, and
            its state is neither read nor written."""
            return greedy_chunk(_decode_step, (cfg, stacked, embed, fnorm),
                                chunk, tok, tables, lens, pool)

        state_dtype = jnp.dtype(cfg.dtype)
        hpack = lane_pack(cfg.mamba_n_heads, cfg.mamba_d_head)
        return PagedPrograms(
            prefill_paged=prefill_paged,
            decode_chunk_paged=decode_chunk_paged,
            kv_layers=cfg.n_attention, kv_heads=kvh // pack,
            head_dim=pack * hd,
            slot_state=lambda slots: (
                jax.ShapeDtypeStruct(
                    (cfg.n_mamba, slots, cfg.mamba_n_heads // hpack,
                     cfg.mamba_d_state, hpack * cfg.mamba_d_head),
                    jnp.float32),
                jax.ShapeDtypeStruct(
                    (cfg.n_mamba, slots, cfg.mamba_d_conv - 1,
                     cfg.conv_dim), state_dtype)),
            chunks_per_block=max(1, prefill_block // cfg.mamba_chunk_size),
            unsupported={
                "prefix_cache": "a prefix hit needs the recurrent state "
                                "at the page boundary, and no snapshot "
                                "is kept",
                "paged=False": "the recurrent state lives per slot "
                               "beside the page pool",
                "chunked_prefill": "a prompt's chunks would have to "
                                   "carry the state between steps",
                "spec_decode": "a rejected draft would have to roll the "
                               "state back",
                "kv_dtype='int8'": "the pool's heads are packed pairs",
                "mesh": "the state arrays have no sharding rule"})

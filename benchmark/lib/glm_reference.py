"""The plain reference of a ``glm_moe_dsa`` configuration (GLM-5) over the
share of the experts and of the vocabulary that the configuration's file
gives the chip: straightforward jax.numpy, float32, matmul precision
"highest". Keys and values are EXPANDED from the latent a head, the
indexer's full [t, u] scores are computed, ``top_k`` gives each query
its ``index_topk``-th largest, and the main attention is a dense softmax
under that mask; the experts are a dense sum over the held ones with
weight zero where an expert was not chosen, plus the shared expert. No
cache, no pages, no absorption, no gather, no grouped product. It imports
nothing of the program and is handed nothing the program made: weights
come from the seed (lib/glm_weights.py), one layer at a time, cast up
from what is stored. Queries go in blocks and heads in groups, so that
the masks and scores of a 49 k prompt fit one chip, and a sequence's
queries in four parts, each against the keys up to its own end (what lies
behind a query is masked in any case).

    x = embed[ids]
    each layer:  h = x + attn(rms(x, g1));  y = h + ffn(rms(h, g2))
    query:       c_q = rms(n W_dq, g_q); q = c_q W_uq as H x (nope | rope)
    latent:      [c_kv | k_r] = n W_dkv; c_kv = rms(c_kv, g_kv);
                 [k_nope | v] = c_kv W_ukv as H x (nope | v); k = k_nope | k_r,
                 k_r the same for every head
    rope:        base rope_parameters.rope_theta, interleaved pairs
                 (x0,x1),(x2,x3),.., on q_rope and k_r
    indexer:     qI = c_q W_qI as Hi x di; kI = LayerNorm(n W_kI) (gain, bias,
                 eps 1e-6); rope on the first qk_rope_head_dim of both;
                 w = n W_w / sqrt(Hi di);
                 I[t,u] = sum_j w[t,j] relu(qI[t,j] . kI[u]), u <= t;
                 allowed(t) = the index_topk largest I[t,u] over u <= t, of
                 equal scores the earliest (every u <= t while t < index_topk)
    attention:   s[t,u] = q[t] . k[u] / sqrt(nope + rope); softmax over
                 allowed(t); o = p v as H x v; W_o
    dense ffn:   W_d(silu(n W_g) * (n W_u))
    expert ffn:  s = sigmoid(n W_r) in float32 over all the router's experts;
                 chosen = top-k of s + b; w_e = s_e / sum over chosen of s,
                 times routed_scaling_factor; shared(n) + sum over the chosen
                 experts HELD HERE of w_e W_d,e(silu(n W_g,e) * (n W_u,e));
                 what the absent experts would add is left out
    logits = rms(y, g_f) W_head, untied, over the held slice of the vocabulary

Two controls of "How correct is decided" (``served_gaps(...,
control=True)``; which one, the tools say by ``GLM_REFERENCE_CONTROL``):
``int8`` (the default): every matrix that multiplies activations rounded
to int8 per output channel, the arithmetic in bfloat16 at the default
precision (the router stays float32, as the configuration states it);
``recent``: float32 as the reference, but the allowed set of a query is
the ``index_topk`` most recent tokens, a wrong selection."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import glm_weights as W
from .reference import (POS_BUCKET, _gap_below_best, _matmul_precision,
                        _pad_to, _rms)

SEQ_BUCKET = 4096       # sequences are padded to a multiple of this
Q_BLOCK = 256           # queries whose attention scores are alive at a time
I_BLOCK = 64            # queries whose indexer scores [., Hi, keys] are
HEAD_GROUP = 8          # heads whose expanded keys and values are
T_BLOCK = 2048          # tokens a feed-forward pass takes at a time
PARTS = 4               # parts of a sequence's queries, each against the
#                         keys up to its own end

FLOAT32 = ("router", "router_bias")


def _fake_int8(w):
    """Round [.., in, out] matrices to int8 per output channel and back."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _cast(leaves, precision):
    if precision == "float32":
        return {k: v.astype(jnp.float32) for k, v in leaves.items()}
    return {k: v if k in FLOAT32 else
            (_fake_int8(v) if v.ndim >= 2 and k != "embed_tokens"
             else v).astype(jnp.bfloat16) for k, v in leaves.items()}


def _rope_pairs(x, positions, theta):
    """x [s, .., r], every dimension turned: interleaved pairs."""
    r = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions[:, None].astype(jnp.float32) * freqs
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), r // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_first(x, positions, theta, rot):
    return jnp.concatenate([_rope_pairs(x[..., :rot], positions, theta),
                            x[..., rot:]], axis=-1)


def _layer_norm(x, g, b, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def index_scores(cfg, lp, n, c_q):
    """What the indexer needs of a sequence: (qI [s, Hi, di], kI [s, di],
    w [s, Hi])."""
    z = W.sizes(cfg)
    s, positions = n.shape[0], jnp.arange(n.shape[0])
    qi = _rope_first((c_q @ lp["w_qi"]).reshape(s, z["hi"], z["di"]),
                     positions, z["theta"], z["rope"])
    ki = _rope_first(_layer_norm(n @ lp["w_ki"], lp["ki_ln_g"],
                                 lp["ki_ln_b"]), positions, z["theta"],
                     z["rope"])
    w = (n @ lp["w_wi"]).astype(jnp.float32) * (z["hi"] * z["di"]) ** -0.5
    return qi, ki, w


def _allowed(cfg, qi, w, ki, q0, selection):
    """[queries, keys] bool: the keys each of the queries at positions
    ``q0..`` (qi [q, Hi, di], w [q, Hi]) may attend to among ``ki``
    [keys, di], the sequence's first. ``indexer``: the ``index_topk``
    largest of the indexer's scores over u <= t, of equal scores the
    earliest; ``recent``: the ``index_topk`` most recent (the wrong
    selection of the second control)."""
    z = W.sizes(cfg)
    s = ki.shape[0]
    keys = jnp.arange(s)

    def block(xs):
        qb, wb, at0 = xs                                # [I, Hi, di], [I, Hi]
        at = (at0 + jnp.arange(qb.shape[0]))[:, None]
        causal = keys[None, :] <= at
        if selection == "recent":
            return causal & (keys[None, :] > at - z["topk"])
        sc = jnp.einsum("shd,td->sht", qb, ki).astype(jnp.float32)
        score = (jax.nn.relu(sc) * wb[..., None]).sum(axis=1)
        score = jnp.where(causal, score, -jnp.inf)
        k = min(z["topk"], s)
        kth = jax.lax.top_k(score, k)[0][:, -1:]
        # equal scores at the k-th place: the earliest tokens, as top_k
        above, tie = score > kth, causal & (score == kth)
        room = k - above.sum(axis=1, keepdims=True)
        return above | (tie & (jnp.cumsum(tie, axis=1) <= room))

    nb = qi.shape[0] // I_BLOCK
    out = jax.lax.map(block, (qi.reshape(nb, I_BLOCK, *qi.shape[1:]),
                              w.reshape(nb, I_BLOCK, -1),
                              q0 + jnp.arange(nb) * I_BLOCK))
    return out.reshape(qi.shape[0], s)


def allowed_keys(cfg, lp, n, c_q, selection="indexer"):
    """[s, s] bool: the keys each query of a sequence may attend to."""
    qi, ki, w = index_scores(cfg, lp, n, c_q)
    return _allowed(cfg, qi, w, ki, 0, selection)


def _attend(cfg, lp, c_q, c_kv, k_r, allowed, q0):
    """The queries at positions ``q0..`` (c_q [q, q_lora_rank]) against
    the sequence's first keys (c_kv [keys, rank], k_r [keys, rope]) under
    ``allowed`` [q, keys]: keys and values expanded a group of heads at a
    time, dense scores, a masked softmax; [q, d]."""
    z = W.sizes(cfg)
    q_n, s = c_q.shape[0], c_kv.shape[0]
    h, nope, rope, hdv, rank = (z["h"], z["nope"], z["rope"], z["hdv"],
                                z["rank"])
    positions = q0 + jnp.arange(q_n)
    allowed = allowed.reshape(q_n // Q_BLOCK, Q_BLOCK, s)
    g = min(HEAD_GROUP, h)
    groups = lambda w, axis: jnp.moveaxis(
        w.reshape(*w.shape[:axis], h // g, g, *w.shape[axis + 1:]), axis, 0)
    w_uq = groups(lp["w_uq"].reshape(-1, h, nope + rope), 1)
    w_ukv = groups(lp["w_ukv"].reshape(rank, h, nope + hdv), 1)
    w_o = groups(lp["wo"].reshape(h, hdv, -1), 0)

    def group(acc, ws):
        wq, wkv, wo = ws
        q = jnp.einsum("sq,qgd->sgd", c_q, wq)
        q = jnp.concatenate([q[..., :nope],
                             _rope_pairs(q[..., nope:], positions,
                                         z["theta"])], axis=-1)
        kv = jnp.einsum("sr,rgd->sgd", c_kv, wkv)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r[:, None, :], (s, g, rope))], axis=-1)
        v = kv[..., nope:]

        def block(xs):
            qb, ok = xs                                 # [Q, g, hd], [Q, s]
            scores = jnp.einsum("sgd,tgd->gst", qb, k).astype(jnp.float32)
            scores = jnp.where(ok[None], scores / np.sqrt(nope + rope),
                               -jnp.inf)
            p = jax.nn.softmax(scores, axis=-1).astype(qb.dtype)
            return jnp.einsum("gst,tgd->sgd", p, v)

        o = jax.lax.map(block, (q.reshape(q_n // Q_BLOCK, Q_BLOCK, g, -1),
                                allowed))
        return acc + jnp.einsum("sgv,gvd->sd", o.reshape(q_n, g, hdv),
                                wo), None

    out, _ = jax.lax.scan(
        group, jnp.zeros((q_n, lp["wo"].shape[-1]), c_q.dtype),
        (w_uq, w_ukv, w_o))
    return out


def _attention(cfg, lp, n, selection):
    """One layer's attention over a whole sequence, the queries in
    ``PARTS`` parts, each against the keys up to its own end (what lies
    behind a query is masked in any case: the parts only leave out work
    whose result a mask would discard)."""
    z = W.sizes(cfg)
    s, eps = n.shape[0], cfg["rms_norm_eps"]
    c_q = _rms(n @ lp["w_dq"], lp["q_ln"], eps)
    ckv = n @ lp["w_dkv"]
    c_kv = _rms(ckv[:, :z["rank"]], lp["kv_ln"], eps)
    k_r = _rope_pairs(ckv[:, z["rank"]:], jnp.arange(s), z["theta"])
    qi, ki, w = index_scores(cfg, lp, n, c_q)
    parts = PARTS if s % (PARTS * max(Q_BLOCK, I_BLOCK)) == 0 else 1
    out = []
    for p in range(parts):
        a, b = p * s // parts, (p + 1) * s // parts
        allowed = _allowed(cfg, qi[a:b], w[a:b], ki[:b], a, selection)
        out.append(_attend(cfg, lp, c_q[a:b], c_kv[:b], k_r[:b], allowed, a))
    return jnp.concatenate(out)


def route(cfg, router, bias, n):
    """[s, experts] float32: an expert's weight for each token, zero
    where the token did not choose it."""
    z = W.sizes(cfg)
    scores = jax.nn.sigmoid(jnp.dot(n.astype(jnp.float32), router,
                                    precision="highest"))
    _, chosen = jax.lax.top_k(scores + bias, z["top_k"])
    picked = jax.nn.one_hot(chosen, z["experts"], dtype=jnp.float32).sum(1)
    weights = scores * picked
    weights = weights / weights.sum(-1, keepdims=True)
    return weights * cfg["routed_scaling_factor"]


def _swiglu(n, wg, wu, wd):
    return (jax.nn.silu(n @ wg) * (n @ wu)) @ wd


def _experts(cfg, stored, lp, n, precision):
    """The shared expert plus the held experts' part: every held expert
    over every token, its weight zero where it was not chosen. The
    experts are cast up one at a time."""
    z = W.sizes(cfg)
    weights = jax.lax.dynamic_slice_in_dim(
        route(cfg, lp["router"], lp["router_bias"], n), z["first"],
        z["held"], axis=1)

    def one(acc, xs):
        e = _cast(dict(zip(("we_gate", "we_up", "we_down"), xs[:3])),
                  precision)
        y = _swiglu(n, e["we_gate"], e["we_up"], e["we_down"])
        return acc + xs[3][:, None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        one, _swiglu(n, lp["ws_gate"], lp["ws_up"], lp["ws_down"]),
        (stored["we_gate"], stored["we_up"], stored["we_down"], weights.T))
    return out


def _ffn(cfg, stored, lp, h, kind, precision):
    """h + ffn(rms(h)), ``T_BLOCK`` tokens at a time."""
    def some(hb):
        n = _rms(hb, lp["post_ln"], cfg["rms_norm_eps"])
        if kind == "dense":
            return hb + _swiglu(n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return hb + _experts(cfg, stored, lp, n, precision)

    t = min(T_BLOCK, h.shape[0])
    return jax.lax.map(some, h.reshape(-1, t, h.shape[1])).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "precision",
                                             "selection"))
def _layer_step(stored, x, cfg_items, kind, precision, selection):
    cfg = W.cfg_of(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        lp = _cast({k: v for k, v in stored.items()
                    if not k.startswith("we_")}, precision)
        h = x + _attention(cfg, lp, _rms(x, lp["input_ln"],
                                         cfg["rms_norm_eps"]), selection)
        return _ffn(cfg, stored, lp, h, kind, precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind"))
def _stored_layer(key, layer, cfg_items, kind):
    return W.make_layer(key, W.cfg_of(cfg_items), layer, kind, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _embed(key, tokens, cfg_items, precision):
    top = _cast(W.make_top(key, W.cfg_of(cfg_items), jnp.bfloat16,
                           only=("embed_tokens",)), precision)
    return jnp.take(top["embed_tokens"], tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(key, x, positions, cfg_items, precision):
    """float32 logits at ``positions``, over the held vocabulary."""
    cfg = W.cfg_of(cfg_items)
    with jax.default_matmul_precision(_matmul_precision(precision)):
        top = _cast(W.make_top(key, cfg, jnp.bfloat16,
                               only=("final_norm", "lm_head")), precision)
        y = _rms(x[positions], top["final_norm"], cfg["rms_norm_eps"])
        return (y @ top["lm_head"]).astype(jnp.float32)


def logits_of(seed, cfg, tokens, positions, precision="float32",
              selection="indexer"):
    """Logits [len(positions), vocab] at ``positions`` of one sequence
    ``tokens`` [s], by a full forward pass, layer by layer, each layer's
    leaves made from the seed when its turn comes (all six at once would
    not leave the pass its room). Everything is causal, so the zeros the
    sequence is padded with change nothing at or before its last real
    token."""
    key, items = W.seed_key(seed), W.model_items(cfg)
    bucket = SEQ_BUCKET
    for b in (Q_BLOCK, I_BLOCK, T_BLOCK):       # every block divides it
        bucket = int(np.lcm(bucket, b))
    tokens = _pad_to(np.asarray(tokens, np.int32), bucket)
    n = len(positions)
    positions = _pad_to(np.asarray(positions, np.int32), POS_BUCKET)
    x = _embed(key, jnp.asarray(tokens), items, precision)
    for layer, kind in enumerate(W.kinds(cfg)):
        x = _layer_step(_stored_layer(key, layer, items, kind), x, items,
                        kind, precision, selection)
    return _head(key, x, jnp.asarray(positions), items, precision)[:n]


def served_gaps(seed, cfg, sequence, n_prompt, control=False):
    """For one finished request (``sequence`` = prompt + served tokens):
    how far each served token's float32 reference logit lies below the
    reference's best at that position. With ``control`` also the same
    for the token the control puts first at each position."""
    sequence = np.asarray(sequence, np.int32)
    positions = np.arange(n_prompt - 1, sequence.size - 1)
    ref = logits_of(seed, cfg, sequence[:-1], positions)
    out = {"served": _gap_below_best(ref, sequence[n_prompt:])}
    if control:
        how = os.environ.get("GLM_REFERENCE_CONTROL", "int8")
        kw = {"selection": "recent"} if how == "recent" \
            else {"precision": how}
        low = logits_of(seed, cfg, sequence[:-1], positions, **kw)
        out["control"] = _gap_below_best(ref, np.asarray(jnp.argmax(low, -1)))
    return out

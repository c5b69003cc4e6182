"""The GLM-5 family (``glm_moe_dsa``) through the paged engine, on the CPU
at debug widths (hidden 64, one dense and four expert layers, 4 heads
over a latent of 16 + 8, an indexer of 2 heads of 16 that keeps the top
8, 16 experts of which 4 are held, top 2, one shared): the system
against the benchmark's plain reference (``benchmark/lib/glm_reference
.py``: expanded keys and values, the indexer's full scores, ``top_k``, a
mask, a dense sum over the held experts; no pages, no absorption, no
gather) on weights from a seed; the absorbed form against the expanded;
the chosen set against ``top_k``; a wrong selection seen; the share of
the experts against the uncut layer; a pool too small for its rows; what
the engine refuses for such a model; the counters; and the decode step's
ways by width (a table of 168 columns: 32 | 64 | 128 | 168) against the
reference and against the full-width path it replaced."""

import pathlib
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import glm_program, glm_reference as R  # noqa: E402
from benchmark.lib import glm_weights as W  # noqa: E402
from harness import (cold_prefill_at_blocks, drive,  # noqa: E402
                     latent_prefill_against_plain)
from paddle_tpu.inference.serving import DecodeEngine  # noqa: E402
from paddle_tpu.models import glm_moe_dsa as G  # noqa: E402
from paddle_tpu.models.paged_stack import _token_insert  # noqa: E402

# the reference pads a sequence to shapes it compiles once; the cell's
# are 4096 tokens, these tests' sequences are under 64
R.SEQ_BUCKET, R.Q_BLOCK, R.I_BLOCK, R.T_BLOCK, R.HEAD_GROUP = 32, 16, 8, 16, 2

SEED = 5
TOPK = 8
CFG = dict(
    name="debug-glm", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
    index_head_dim=16, index_topk=TOPK, vocab_size=256, n_routed_experts=4,
    expert_share={"rank": 0, "of": 4}, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=2.5, scoring_func="sigmoid",
    norm_topk_prob=True, n_group=1, topk_group=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    program={"model": {"dtype": "bfloat16"}})
# one engine shape for every case, so that its two programs compile once
# (a cold prefill walks the window in blocks of 32 rows)
ENGINE = dict(capacity=2, s_max=64, chunk=4, block_size=8, n_blocks=17,
              prefix_cache=False)
# a table long enough for three widths under its own length: 21 pages of
# 8 columns, so a row is scored over 32, 64, 128 or 168 of them
WIDE = dict(capacity=4, s_max=160, chunk=4, block_size=8, n_blocks=85,
            prefix_cache=False)
WIDTHS = (32, 64, 128, 168)
_MODEL, _SERVED, _WIDE = [], [], []


def model():
    """The builder's model on the seeded bfloat16 leaves, computing in
    float32: what the float32 reference reads, value for value."""
    if not _MODEL:
        m = glm_program.build_model(CFG, SEED)
        m.eval()
        for p in m.parameters():
            p._in_place_update(p._value.astype(jnp.float32))
        m.config.dtype = "float32"
        _MODEL.append(m)
    return _MODEL[0]


def prompts(*sizes, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, CFG["vocab_size"], (n,)).astype(np.int32)
            for n in sizes]


def served_gap(seq, n_prompt):
    """How far below the reference's best the served tokens lie."""
    return float(R.served_gaps(SEED, CFG, seq, n_prompt)["served"].max())


def layer_leaves(layer, kind, cfg=CFG):
    """(the reference's float32 leaves of one layer, the program's)."""
    stored = W.make_layer(W.seed_key(SEED), cfg, layer, kind, jnp.bfloat16)
    ref = {k: v.astype(jnp.float32) for k, v in stored.items()}
    prog = {k: v for k, v in ref.items() if k != "w_ukv"}
    prog.update(W.split_ukv(cfg, ref["w_ukv"]))
    return ref, prog


def served():
    """One profiled engine that has served three requests: a prompt under
    ``index_topk`` whose context crosses it while decoding beside a
    prompt of two prefill blocks, both under the selection's mask, then
    a third in the slot the first to retire gave up."""
    if not _SERVED:
        eng = DecodeEngine(model(), **ENGINE, profile=True)
        # what the backend compiles, or reads from a compile cache,
        # while the engine reads its programs' scopes: nothing, the
        # executable is the one the launch before it built
        eng.scope_compiles, inside, note = [], [], eng._note_scopes
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _, **kw: eng.scope_compiles.append(event)
            if inside and ("backend_compile" in event
                           or "compilation_cache" in event) else None)

        def counted(*args):
            inside.append(args[0])
            try:
                note(*args)
            finally:
                inside.pop()

        eng._note_scopes = counted
        ps = prompts(5, 45, 21)
        news = (9, 13, 12)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, news)]
        drive(eng)
        _SERVED.append((eng, ps, news, reqs))
    return _SERVED[0]


def logits_case():
    """The two programs' logits against the reference's full forward
    pass, value for value: a cold prefill of 57 tokens in four blocks of
    16 (causal under ``index_topk``, then the selection as a mask over
    the causal pass), then 6 decode steps; and a prompt of 5 whose context crosses ``index_topk``
    while it decodes."""
    m = model()
    cfg = m.config
    st = {n: m._parameters[n]._value for n in m._stacked_names()}
    top = [m._parameters[n]._value
           for n in ("embed_tokens", "final_norm", "lm_head")]
    bs, n_pages, s_max, steps = 8, 12, 64, 6
    for n_prompt in (57, 5):
        seq, = prompts(n_prompt + steps, seed=n_prompt)
        want = np.asarray(R.logits_of(
            SEED, CFG, seq, np.arange(n_prompt - 1, seq.size)))
        pool = (jnp.zeros((5, n_pages, 1, bs, cfg.latent_lanes)),
                jnp.zeros((5, n_pages, 1, bs, cfg.index_head_dim)),
                jnp.zeros((4,), jnp.int32))
        ids = np.zeros((1, s_max), np.int32)
        ids[0, s_max - n_prompt:] = seq[:n_prompt]
        table = jnp.asarray(np.r_[1:9], jnp.int32)
        logits, pool = jax.jit(
            lambda ids, pad, pool: G._prefill(cfg, st, *top, ids, pad, table,
                                              pool, 16))(
            ids, jnp.asarray([s_max - n_prompt], jnp.int32), pool)
        got = [np.asarray(logits)[0]]
        step = jax.jit(lambda tok, lens, pool: G._decode_step(
            cfg, st, *top, tok, table[None], lens, pool,
            jnp.ones((1,), bool)))
        for i in range(steps):
            logits, pool = step(jnp.asarray(seq[n_prompt + i:][:1]),
                                jnp.asarray([n_prompt + i], jnp.int32), pool)
            got.append(np.asarray(logits)[0])
        assert np.abs(want).max() > 0.05
        np.testing.assert_allclose(np.stack(got), want, atol=3e-6)


def engine_case():
    """Prefill then decoded tokens through pages, indexer and experts
    against the plain reference; both pools are pages of unequal width
    under the one table, and no slot holds state."""
    eng, ps, news, reqs = served()
    for r, p in zip(reqs, ps):
        assert served_gap(r.wait(1), p.size) < 1e-6
    stats = eng.stats()
    assert stats["admitted"] == stats["retired"] == 3
    assert "ssm_row_steps" not in stats         # a recurrent family's own
    assert [s.shape for s in eng._state_specs] == [(4,)]
    assert eng._kp.shape == (5, 17, 1, 8, 128)      # 16 + 8 -> a lane tile
    assert eng._vp.shape == (5, 17, 1, 8, 16)
    assert eng._progs.unsupported.keys() >= {"prefix_cache", "spec_decode"}


def counters_case():
    """The counters read what the lengths say: a decode step scores a
    live row's context in every layer and selects ``min(context,
    index_topk)`` of it; the launches' entries carry both behind the
    device's three, and the device's later fourth behind them: every
    older field stands where it stood."""
    eng, ps, news, reqs = served()
    stats = eng.stats()
    log = stats["launches"]     # [t, kind, units, rows, tokens, *counters]
    decode = [e for e in log if e[1] == "decode"]
    assert sum(e[4] for e in decode) == stats["decode_ctx_tokens"]
    assert stats["dsa_scored_tokens"] == 5 * stats["decode_ctx_tokens"]
    assert 5 * TOPK * stats["decode_row_steps"] * 0.5 \
        < stats["dsa_selected_tokens"] <= 5 * TOPK * stats["decode_row_steps"]
    # the first chunk of the 5-token prompt reads 5, 6, 7, 8 tokens: all
    assert stats["dsa_selected_tokens"] < stats["dsa_scored_tokens"]
    assert log[-1][5:] == [stats["moe_pairs"], stats["moe_expert_visits"],
                           stats["moe_full_stream"],
                           stats["dsa_scored_tokens"],
                           stats["dsa_selected_tokens"],
                           stats["dsa_scored_columns"],
                           stats["moe_stream_rows"]]
    assert stats["moe_stream_rows"] >= stats["moe_pairs"]
    assert all(b[8] - a[8] == (5 * b[4] if b[1] == "decode" else 0)
               for a, b in zip(log, log[1:]))
    fed = sum(p.size + n - 1 for p, n in zip(ps, news))
    assert 0 < stats["moe_pairs"] <= 2 * 4 * fed
    # a trace names events by instruction: the engine says which scope
    # each instruction of its two programs lies under
    scopes = stats["scopes"]
    assert set(scopes) == {"jit_prefill_paged", "jit_decode_chunk_paged"}
    assert eng.scope_compiles == []
    assert {"dsa_index_scores", "dsa_topk", "mla_sparse_decode",
            "moe_shared_ffn", "moe_expert_ffn"} \
        <= set(scopes["jit_decode_chunk_paged"].values())
    assert {"dsa_index_scores", "dsa_topk", "mla_prefill_attn"} \
        <= set(scopes["jit_prefill_paged"].values())
    snap = str(eng.metrics.snapshot())
    for name in ("engine_dsa_scored_tokens_total",
                 "engine_dsa_selected_tokens_total",
                 "engine_dsa_scored_columns_total", "engine_moe_pairs_total",
                 "engine_moe_expert_visits_total",
                 "engine_moe_full_stream_total",
                 "engine_moe_stream_rows_total"):
        assert name in snap


def wide_served():
    """One profiled engine whose table holds three widths under its own
    length, with every decode launch's ``lens`` kept: a prompt under
    ``index_topk`` (slot 0), one that retires after its first chunk and
    leaves slot 1 empty between live ones, one whose context crosses the
    edge of the first width in the middle of a chunk (30, 31 | 32, 33)
    and one in the third width, all in one launch; then, alone, a row
    whose last chunk starts at ``s_max - chunk``."""
    if not _WIDE:
        eng = DecodeEngine(model(), **WIDE, profile=True)
        lens_log, decode = [], eng._decode

        def noting(*args):
            lens_log.append(np.array(args[7]))
            return decode(*args)

        noting.__wrapped__ = getattr(decode, "__wrapped__", decode)
        eng._decode = noting
        ps = prompts(5, 12, 30, 100, 148, seed=7)
        news = (12, 3, 10, 9, 12)
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(ps[:4], news)]
        drive(eng)
        reqs.append(eng.submit(ps[4], max_new_tokens=news[4]))
        drive(eng)
        _WIDE.append((eng, ps, news, reqs, lens_log))
    return _WIDE[0]


def widths_case():
    """Rows of different widths in one launch, an empty slot between
    live ones, a row that changes its width mid-chunk, a context under
    ``index_topk`` and one at ``s_max - chunk``: every served token is
    the reference's."""
    eng, ps, news, reqs, lens_log = wide_served()
    assert G.decode_widths(21 * 8, TOPK, 8) == WIDTHS
    for r, p in zip(reqs, ps):
        assert served_gap(r.wait(1), p.size) < 1e-6
    starts = {tuple(int(v) for v in lens) for lens in lens_log}
    assert (5, 12, 30, 100) in starts           # three widths, one launch
    assert (9, 0, 34, 104) in starts            # an empty slot between
    assert any(max(lens) == WIDE["s_max"] - WIDE["chunk"]
               for lens in lens_log)


def columns_case():
    """``engine_dsa_scored_columns_total`` is the rule's own sum: a live
    row, layer and step counts the least width that holds its context
    and its new token; the two counters ahead of it stand where they
    stood in a launch's entry."""
    eng, ps, news, reqs, lens_log = wide_served()
    stats = eng.stats()
    want = sum(next(w for w in WIDTHS if w > pos + i)
               for lens in lens_log for pos in lens if pos
               for i in range(WIDE["chunk"]))
    assert stats["dsa_scored_columns"] == 5 * want
    assert stats["dsa_scored_tokens"] == 5 * stats["decode_ctx_tokens"]
    assert stats["dsa_scored_tokens"] < stats["dsa_scored_columns"]
    log = [e for e in stats["launches"] if e[1] == "decode"]
    assert len(log) == len(lens_log)
    assert log[-1][8:11] == [stats["dsa_scored_tokens"],
                             stats["dsa_selected_tokens"],
                             stats["dsa_scored_columns"]]
    assert "engine_dsa_scored_columns_total" in str(eng.metrics.snapshot())
    # on the host as in the program: one rule
    assert G._width_index(np.asarray([0, 31, 32, 127, 128, 167]),
                          WIDTHS).tolist() == [0, 0, 1, 2, 3, 3]


def full_width_attention(cfg, lp, x, l, kp, vp, tables, lens):
    """The decode step's attention as it stood before the ways by width:
    every slot's indexer pages to the table's length, ``top_k`` over all
    of them, the chosen latents read token by token."""
    b = x.shape[0]
    n_layers, n_pages, _, bs, lanes = kp.shape
    s = tables.shape[1] * bs
    qc, lat, qi, ki, wi = G._project(cfg, lp, x, lens)
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    kp = _token_insert(kp, l, page, lens % bs, lat[:, None])
    vp = _token_insert(vp, l, page, lens % bs, ki[:, None])
    keys = jnp.take(vp.reshape(n_layers * n_pages, bs, vp.shape[-1]),
                    l * n_pages + tables, axis=0)
    sc = jnp.where(jnp.arange(s)[None, :] <= lens[:, None],
                   G._index_scores(qi, wi, keys.reshape(b, s, -1)), -jnp.inf)
    vals, idx = jax.lax.top_k(sc, min(cfg.index_topk, s))
    at = jnp.take_along_axis(tables, idx // bs, axis=1)
    sel = jnp.take(kp.reshape(n_layers * n_pages * bs, lanes),
                   (l * n_pages + at) * bs + idx % bs, axis=0)
    o_lat = G._sparse_attend(cfg, qc, sel, vals > -jnp.inf)
    return G._out_proj(cfg, lp, o_lat), idx, vals


def ties_case(gather_from=None):
    """Equal scores at the cut: with three distinct indexer keys over a
    row's columns the best class holds more than ``index_topk`` tokens,
    and the step keeps the earliest of them, as ``top_k`` does and as
    ``chosen_case`` holds for the prefill; in every width, beside an
    empty slot, to the full-width path's output."""
    if gather_from is None:     # every row by the masked pass, then the
        for columns in (10 ** 6, 64):   # rows from 64 columns by index
            with mock.patch.object(G, "GATHER_FROM", columns):
                ties_case(columns)
        return
    cfg = model().config
    _, prog = layer_leaves(2, "moe")
    layer, n_pages, bs = 2, 64, 8
    lens = jnp.asarray([20, 0, 50, 100, 140], jnp.int32)
    tables = jnp.asarray(1 + np.arange(5 * 21).reshape(5, 21) % (n_pages - 1),
                         jnp.int32)
    key = jax.random.key(9)
    x = jax.random.normal(key, (5, CFG["hidden_size"]))
    kp = jax.random.normal(key, (5, n_pages, 1, bs, cfg.latent_lanes))
    three = jax.random.normal(key, (3, cfg.index_head_dim))
    vp = jnp.broadcast_to(three[jnp.arange(n_pages * bs) % 3].reshape(
        n_pages, 1, bs, -1), (5, n_pages, 1, bs, cfg.index_head_dim))
    got, _, _ = jax.jit(lambda *a: G._decode_attention(
        cfg, prog, x, layer, *a, lens, G._live_rows(lens > 0)))(kp, vp,
                                                                 tables)
    want, idx, vals = jax.jit(lambda *a: full_width_attention(
        cfg, prog, x, layer, *a, lens))(kp, vp, tables)
    live = np.flatnonzero(np.asarray(lens))
    # the cut does fall among equals, and the kept of them are the
    # earliest: columns u, u + 3, u + 6, .. share a key
    for r in live:
        v, i = np.asarray(vals[r]), np.asarray(idx[r])
        cut = np.sort(i[v == v.min()])
        equals = np.arange(cut[0] % 3, int(lens[r]), 3)
        assert equals.size > cut.size, r
        assert cut.tolist() == equals[:cut.size].tolist(), r
    assert np.abs(np.asarray(want)[live]).max() > 0.01
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6)
    assert not np.asarray(got)[1].any()         # nothing read, nothing added


def kernel_case():
    """The chip's selection of one row (``kernels/topk_mask.py``, here
    in interpret mode) against ``_chosen_mask``, the same passes in XLA
    and what the decode step takes on the CPU: a width that fills whole
    tiles and one that does not, a context under ``k`` (everything
    seen is kept, nothing past it), and scores rounded so that the cut
    falls among equals (the earliest kept)."""
    from paddle_tpu.kernels import topk_mask
    for w, k, pos, equals in ((168, TOPK, 100, False), (168, TOPK, 5, False),
                              (168, TOPK, 150, True), (2048, 64, 2047, True),
                              (1030, 300, 1000, True)):
        sc = jax.random.normal(jax.random.key(w + pos), (1, w))
        sc = jnp.round(sc * 4) / 4 if equals else sc
        at = jnp.asarray([pos], jnp.int32)
        seen = jnp.arange(w)[None, :] <= pos
        want = np.asarray(seen & G._chosen_mask(
            jnp.where(seen, sc, -jnp.inf), k))
        assert want.sum() == min(k, pos + 1)
        got = topk_mask.chosen_mask_pallas(sc, at, k, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(G._row_chosen(sc, at, k)),
                                      want)
        if equals:      # the cut does fall among equals
            kth = np.sort(np.asarray(sc)[0, :pos + 1])[-k]
            assert (np.asarray(sc)[0, :pos + 1] == kth).sum() \
                > (np.asarray(sc)[0][want[0]] == kth).sum()
        # and the mask's columns without a sort, for the way by index
        cols, real = G._mask_columns(jnp.asarray(want[0]), k)
        assert np.asarray(cols)[np.asarray(real)].tolist() \
            == np.flatnonzero(want[0]).tolist()


def _layer_inputs(s=24, seed=3):
    x = jax.random.normal(jax.random.key(seed), (s, CFG["hidden_size"]))
    ref, prog = layer_leaves(2, "moe")
    n = R._rms(x, ref["input_ln"], CFG["rms_norm_eps"])
    return x, n, ref, prog


def absorbed_case():
    """The absorbed form (the query carried into the latent space, the
    output carried out of it, one latent a token for all heads) equals
    the expanded form of the reference, keys and values a head, under
    the same allowed sets."""
    cfg = model().config
    x, n, ref, prog = _layer_inputs(s=16)
    c_q = R._rms(n @ ref["w_dq"], ref["q_ln"], CFG["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R._attention(CFG, ref, n, "indexer"))
        allowed = R.allowed_keys(CFG, ref, n, c_q)
    qc, lat, *_ = G._project(cfg, prog, x, jnp.arange(16))
    got = G._out_proj(cfg, prog, G._sparse_attend(
        cfg, qc, jnp.broadcast_to(lat[None], (16, *lat.shape)), allowed))
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(got, want, atol=2e-6)


def chosen_case():
    """The tokens a decode step keeps are the reference's ``top_k`` of
    the indexer's scores, at every position past ``index_topk``."""
    cfg = model().config
    x, n, ref, prog = _layer_inputs()
    c_q = R._rms(n @ ref["w_dq"], ref["q_ln"], CFG["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.allowed_keys(CFG, ref, n, c_q))
    _, _, qi, ki, wi = G._project(cfg, prog, x, jnp.arange(24))
    sc = jnp.where(jnp.tril(jnp.ones((24, 24), bool)),
                   G._index_scores(qi, wi, ki), -jnp.inf)
    vals, idx = jax.lax.top_k(sc, TOPK)
    for t in range(24):
        got = set(np.asarray(idx[t])[np.asarray(vals[t]) > -np.inf].tolist())
        assert got == set(np.flatnonzero(want[t]).tolist()), t
    assert want[:TOPK].sum() == TOPK * (TOPK + 1) // 2      # all, causal
    assert (want[TOPK:].sum(1) == TOPK).all()
    # and they are not the most recent: the indexer chooses
    recent = np.asarray(R.allowed_keys(CFG, ref, n, c_q, "recent"))
    assert (recent[TOPK:].sum(1) == TOPK).all()
    assert (want[TOPK:] != recent[TOPK:]).any(1).mean() > 0.8


def wrong_selection_case():
    """A selection replaced by "the most recent ``index_topk``" moves the
    logits far past the tolerance the other cases hold: the comparison
    with the reference sees which tokens were chosen."""
    seq, = prompts(40, seed=11)
    at = np.arange(TOPK + 4, 39)
    right = np.asarray(R.logits_of(SEED, CFG, seq, at))
    wrong = np.asarray(R.logits_of(SEED, CFG, seq, at, selection="recent"))
    assert np.abs(right - wrong).max(axis=1).min() > 1e-4
    assert np.abs(right - wrong).max() > 1e-2
    early = np.arange(0, TOPK)          # under index_topk nothing differs
    np.testing.assert_allclose(
        R.logits_of(SEED, CFG, seq, early, selection="recent"),
        R.logits_of(SEED, CFG, seq, early), atol=1e-6)


def share_case():
    """The share test: the routed parts that all 4 shares of the debug
    model give for one expert layer, plus the shared expert once, add up
    to what the uncut reference gives for the whole layer, and no share
    alone does."""
    layer = 2
    x = jax.random.normal(jax.random.key(3), (24, CFG["hidden_size"]))
    uncut = dict(CFG, n_routed_experts=16, expert_share={"rank": 0, "of": 1})
    ref, _ = layer_leaves(layer, "moe", uncut)
    n = R._rms(x, ref["post_ln"], CFG["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R._experts(uncut, ref, ref, n, "float32"))
        shared = np.asarray(R._swiglu(n, ref["ws_gate"], ref["ws_up"],
                                      ref["ws_down"]))
    parts = []
    for rank in range(4):
        cfg = dict(CFG, expert_share={"rank": rank, "of": 4})
        mcfg = glm_program.glm_config(cfg, dtype="float32")
        assert mcfg.held_experts == (4 * rank, 4)
        _, held = layer_leaves(layer, "moe", cfg)
        # the one stack of all layers' held experts, this layer second
        w = {k: jnp.concatenate([jnp.zeros_like(held[k]), held[k]])
             for k in ("we_gate", "we_up", "we_down")}
        out, counts = G._ffn(mcfg, w, held, "moe", 1, x,
                             jnp.ones((24,), bool), jnp.zeros((4,), jnp.int32))
        parts.append(np.asarray(out - x) - shared)
        assert 0 < int(counts[0]) < 2 * 24 and 0 < int(counts[1]) <= 4
    assert np.abs(whole - shared).max() > 0.01
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-6)
    assert all(np.abs(p + shared - whole).max() > 1e-3 for p in parts)


def small_pool_case():
    """A pool too small for its rows: the row evicted for a higher
    priority gives its pages up and resumes, by recomputing its prefill
    (latents, indexer keys and all), to the tokens it would have served
    undisturbed."""
    low, high = prompts(20, 33, seed=3)
    calm = DecodeEngine(model(), **ENGINE)
    r = calm.submit(low, max_new_tokens=16)
    drive(calm)
    want = r.wait(1)
    eng = DecodeEngine(model(), **{**ENGINE, "n_blocks": 7})
    r_low = eng.submit(low, max_new_tokens=16)
    eng.admit([])
    eng.decode_once()
    r_high = eng.submit(high, max_new_tokens=8, priority=1)
    drive(eng)
    stats = eng.stats()
    assert stats["preempted"] >= 1 and stats["pool"]["free"] == 6
    np.testing.assert_array_equal(r_low.wait(1), want)
    assert served_gap(r_high.wait(1), high.size) < 1e-6


def block_case():
    """A cold prefill at blocks of 512 rows against the same prompt at
    256, most of it past ``index_topk`` tokens: the logits, the latent
    pages and the indexer's."""
    cold_prefill_at_blocks(model(), G._prefill)


@pytest.mark.parametrize("case", [
    logits_case, engine_case, counters_case, absorbed_case, chosen_case,
    wrong_selection_case, share_case, small_pool_case, widths_case,
    columns_case, ties_case, kernel_case, block_case],
    ids=lambda f: f.__name__)
def test_glm_moe_dsa(case):
    case()


@pytest.mark.parametrize("start, pad, topk", [
    # every query of the block has seen fewer tokens than it may keep
    # (the mask then holds columns it may not see: the kernel's own
    # compare is what keeps them out)
    (32, 8, 48),
    # the block's first query has seen exactly ``topk``, the others more
    (48, 9, 40),
    # far more seen than kept, the row's first tile skipped
    (80, 33, 8)],
    ids=["fewer_seen_than_kept", "exactly_as_many", "eight_of_many"])
def test_prefill_kernel_under_a_mask_against_the_plain_pass(start, pad, topk):
    got, want, allowed = latent_prefill_against_plain(
        start, pad, rows=32, keys=32, topk=topk)
    seen = np.asarray(G._block_seen(start + jnp.arange(16), pad, 0, 96))
    kept = (np.asarray(allowed) & seen).sum(axis=1)
    assert kept.tolist() == np.minimum(seen.sum(axis=1), topk).tolist()
    assert (kept[0] == topk) == (start - pad + 1 >= topk)
    assert (np.asarray(allowed) & ~seen).any() == (start - pad + 1 < topk)
    width = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=1e-4 * width)


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("paged=False", dict(paged=False, prefix_cache=False)),
    ("chunked_prefill", dict(chunked_prefill=True, prefix_cache=False)),
    ("spec_decode", dict(spec_decode=True, prefix_cache=False)),
    ("kv_dtype='int8'", dict(kv_dtype="int8", block_size=32,
                             prefix_cache=False)),
    ("mesh", dict(mesh="a mesh", prefix_cache=False)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_family_cannot_serve_raises_at_construction(option, kw):
    if option == "mesh":
        from jax.sharding import Mesh
        kw = {**kw, "mesh": Mesh(np.asarray(jax.devices()[:1]), ("tp",))}
    with pytest.raises(ValueError, match="cannot be served with") as err:
        DecodeEngine(model(), **{**ENGINE, **kw})
    assert option in str(err.value)


@pytest.mark.parametrize("key, value", [
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("rope_type", "yarn"), ("norm_topk_prob", False)])
def test_what_no_published_configuration_sets_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        G.GlmMoeDsaConfig(**{**G.GLM_MOE_DSA_PRESETS["debug"], key: value})

"""The DeepSeek-V3 family (``deepseek_v3``) through the paged engine, on
the CPU at debug widths (hidden 64, one dense and four expert layers, 4
heads over a latent of 16 + 8, 32 experts in 4 groups of which 2 stay, 4
of them held, top 4, one shared; YaRN over an original context of 64):
the system against the benchmark's plain reference
(``benchmark/lib/deepseek_reference.py``: expanded keys and values, a
dense softmax, a dense sum over the held experts; no pages, no
absorption, no kernel) on weights from a seed; the decode kernel in
interpret mode against the plain path; YaRN's numbers; group-limited
routing against a literal loop; the share of the experts against the
uncut layer; what the engine refuses; and that the code this family
shares with ``glm_moe_dsa`` and ``mimo_v2`` left their programs as they
were."""

import functools
import hashlib
import pathlib
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import deepseek_program  # noqa: E402
from benchmark.lib import deepseek_reference as R  # noqa: E402
from benchmark.lib import deepseek_weights as W  # noqa: E402
from harness import (cold_prefill_at_blocks, drive,  # noqa: E402
                     latent_prefill_against_plain, paged_program_hashes)
from paddle_tpu.distributed.fleet.moe import moe_route_held  # noqa: E402
from paddle_tpu.inference.serving import DecodeEngine  # noqa: E402
from paddle_tpu.kernels import latent_attention as LA  # noqa: E402
from paddle_tpu.models import deepseek_v3 as D  # noqa: E402
from paddle_tpu.models import glm_moe_dsa as G  # noqa: E402

# the reference pads a sequence to shapes it compiles once; the cell's
# are 4096 tokens, these tests' sequences are under 64 and share one
R.SEQ_BUCKET, R.Q_BLOCK, R.T_BLOCK, R.HEAD_GROUP = 64, 16, 16, 2

SEED = 7
CFG = dict(
    name="debug-deepseek", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=5, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=256,
    n_routed_experts=4, expert_share={"rank": 0, "of": 8},
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2.5,
    scoring_func="sigmoid", norm_topk_prob=True, n_group=4, topk_group=2,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 64, "type": "yarn"},
    program={"model": {"dtype": "bfloat16"}})
ENGINE = dict(capacity=3, s_max=64, chunk=4, block_size=8, n_blocks=25,
              prefix_cache=False)
_MODEL = []


def model():
    """The builder's model on the seeded bfloat16 leaves, computing in
    float32: what the float32 reference reads, value for value."""
    if not _MODEL:
        m = deepseek_program.build_model(CFG, SEED)
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


def engine_case():
    """Prefill then decoded tokens through the one pool's pages against
    the plain reference: two rows of unlike length beside a slot left
    empty, then a third row in the slot the first to retire gave up,
    while the middle slot stands empty between live ones."""
    eng = DecodeEngine(model(), **ENGINE, profile=True)
    ps, news = prompts(5, 45, 21), (6, 13, 9)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps[:2], news)]
    drive(eng)
    reqs.append(eng.submit(ps[2], max_new_tokens=news[2]))
    drive(eng)
    for r, p in zip(reqs, ps):
        gaps = R.served_gaps(SEED, CFG, r.wait(1), p.size)["served"]
        assert float(gaps.max()) < 1e-6
    stats = eng.stats()
    assert stats["admitted"] == stats["retired"] == 3
    # one kind of page: a latent of 16 + 8 in a lane tile, no second pool
    assert eng._kp.shape == (5, 25, 1, 8, 128) and eng._vp is None
    assert len(eng._pool()) == 2 and eng._n_pool == 2
    assert [s.shape for s in eng._state_specs] == [(5,)]
    assert deepseek_program.kv_bytes_per_block(CFG, 8) == 5 * 8 * 128 * 2
    # the counters: a decode step reads a live row's context in every layer
    log = stats["launches"]
    assert stats["mla_ctx_tokens"] == 5 * stats["decode_ctx_tokens"] > 0
    assert log[-1][5:] == [stats[n] for n in (
        "moe_pairs", "moe_expert_visits", "moe_full_stream",
        "moe_groups_visited", "mla_ctx_tokens", "moe_stream_rows")]
    assert stats["moe_stream_rows"] >= stats["moe_pairs"]
    units = 4 * (stats["device_steps"] + stats["prefill_blocks"])
    assert 0 < stats["moe_expert_visits"] <= 4 * units
    assert units <= stats["moe_groups_visited"] <= 4 * units
    scopes = stats["scopes"]
    assert {"mla_dense_decode", "moe_group_route", "moe_shared_ffn",
            "moe_expert_ffn"} <= set(scopes["jit_decode_chunk_paged"].values())
    assert {"mla_prefill_attn", "moe_group_route"} \
        <= set(scopes["jit_prefill_paged"].values())


def logits_case():
    """The two programs' logits against the reference's full forward
    pass, value for value: a cold prefill of 37 tokens in three blocks
    of 16, then 5 decode steps of two rows of unlike length beside an
    empty slot."""
    m = model()
    cfg = m.config
    st = {n: m._parameters[n]._value for n in m._stacked_names()}
    top = [m._parameters[n]._value
           for n in ("embed_tokens", "final_norm", "lm_head")]
    bs, s_max, steps = 8, 64, 5
    sizes = (37, 6)
    seqs = prompts(*(n + steps for n in sizes), seed=3)
    want = [np.asarray(R.logits_of(SEED, CFG, seq,
                                   np.arange(n - 1, seq.size)))
            for n, seq in zip(sizes, seqs)]
    pool = (jnp.zeros((5, 20, 1, bs, cfg.latent_lanes)),
            jnp.zeros((5,), jnp.int32))
    tables = np.zeros((3, 8), np.int32)
    tables[0], tables[2] = np.r_[1:9], np.r_[9:17]
    prefill = jax.jit(lambda ids, pad, table, pool: G._prefill(
        cfg, st, *top, ids, pad, table, pool, 16, attend=D._prefill_attend))
    got = [[], []]
    for i, (n, seq) in enumerate(zip(sizes, seqs)):
        ids = np.zeros((1, s_max), np.int32)
        ids[0, s_max - n:] = seq[:n]
        logits, pool = prefill(ids, jnp.asarray([s_max - n], jnp.int32),
                               jnp.asarray(tables[2 * i]), pool)
        got[i].append(np.asarray(logits)[0])
    step = jax.jit(lambda tok, lens, pool: D._decode_step(
        cfg, st, *top, tok, jnp.asarray(tables), lens, pool, lens > 0))
    for i in range(steps):
        tok = jnp.asarray([seqs[0][sizes[0] + i], 0, seqs[1][sizes[1] + i]])
        lens = jnp.asarray([sizes[0] + i, 0, sizes[1] + i], jnp.int32)
        logits, pool = step(tok, lens, pool)
        got[0].append(np.asarray(logits)[0])
        got[1].append(np.asarray(logits)[2])
    for g, w in zip(got, want):
        assert np.abs(w).max() > 0.05
        np.testing.assert_allclose(np.stack(g), w, atol=3e-6)
    assert int(pool[-1][0]) > 0


def kernel_case():
    """The decode kernel (interpret mode) against the plain path: rows
    whose contexts are no multiple of the page or of the kernel's block
    of pages, one of a single token, an empty slot between them, pages
    out of order in the pool, lanes past the latent zero."""
    rng = np.random.RandomState(0)
    L, N, bs, lanes, H, rank, mb = 2, 40, 8, 256, 4, 128, 9
    pages = jnp.asarray(rng.randn(L, N, 1, bs, lanes), jnp.float32)
    q = jnp.asarray(rng.randn(4, H, lanes), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(N - 1)[:4 * mb].reshape(4, mb),
                         jnp.int32)
    lens = jnp.asarray([67, 0, 1, 30], jnp.int32)
    # 4 pages a block: row 0 is two full blocks (one wait each) and a
    # partial one (page by page)
    with mock.patch.object(LA, "_BLOCK_TOKENS", 32):
        got = LA.latent_attention_pallas(q, pages, tables, lens, 1,
                                         rank=rank, scale=0.3,
                                         interpret=True)
    want = LA.latent_attention_reference(q, pages, tables, lens, 1,
                                         rank=rank, scale=0.3)
    assert got.shape == (4, H, rank) and float(jnp.abs(got[1]).max()) == 0
    np.testing.assert_allclose(got, want, atol=2e-5)
    # against a literal softmax over row 0's tokens
    flat = np.asarray(pages)[1, np.asarray(tables[0]), 0].reshape(-1, lanes)
    s = np.asarray(q[0]) @ flat[:67].T * 0.3
    p = np.exp(s - s.max(-1, keepdims=True))
    np.testing.assert_allclose(
        got[0], (p / p.sum(-1, keepdims=True)) @ flat[:67, :rank], atol=2e-5)


def yarn_case():
    """YaRN's frequencies and scale at the published numbers: low 10,
    high 23, the blend between, 1.8738 on the softmax; the program's and
    the reference's agree."""
    published = D.DeepseekV3Config(held_experts=(0, 16))
    assert G.yarn_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    f = np.asarray(published.inv_freq(), np.float64)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    r = (15 - 10) / 13
    np.testing.assert_allclose(f[15], base[15] / 40 * r + base[15] * (1 - r),
                               rtol=1e-6)
    assert abs(G.yarn_mscale(40, 1.0) ** 2 - 1.8738) < 1e-4
    assert abs(published.softmax_scale - 0.07217 * 1.8738) < 2e-5
    ref = dict(CFG, qk_rope_head_dim=64, qk_nope_head_dim=128)
    ref["rope_scaling"] = dict(CFG["rope_scaling"],
                               original_max_position_embeddings=4096)
    np.testing.assert_allclose(np.asarray(R.inv_freq(ref)), f, rtol=1e-6)
    assert abs(W.softmax_scale(ref) - published.softmax_scale) < 1e-9
    # the debug model's blend has a dimension between its ends
    assert G.yarn_range(8, 10000.0, 64, 32, 1) == (0, 2)


def _literal_route(scores, bias, n_group, topk_group, k):
    """One token's chosen experts and weights, as the source states
    them, by loops."""
    e = len(scores)
    per = e // n_group
    choice = [s + b for s, b in zip(scores, bias)]
    group_score = [sum(sorted(choice[g * per:(g + 1) * per])[-2:])
                   for g in range(n_group)]
    kept = sorted(range(n_group), key=lambda g: (-group_score[g], g)
                  )[:topk_group]
    left = [i for i in range(e) if i // per in kept]
    chosen = sorted(left, key=lambda i: (-choice[i], i))[:k]
    total = sum(scores[i] for i in chosen)
    return chosen, [scores[i] / total for i in chosen]


def route_case():
    """Group-limited selection against the literal loop, the program's
    and the reference's; a token whose unrestricted top-k would leave
    its kept groups is among them."""
    rng = np.random.RandomState(2)
    n, e, n_group, topk_group, k = 48, 32, 4, 2, 4
    logits = rng.randn(n, e).astype(np.float32) * 2
    # token 0: the two best experts alone in groups 2 and 3, whose other
    # experts are poor, so that groups 0 and 1 stay and both are out
    logits[0] = np.r_[np.full(16, 1.0), -4 * np.ones(16)]
    logits[0, [16, 24]] = 6.0
    bias = (rng.rand(e).astype(np.float32) - 0.5) * 0.01
    topi, gates, order, sizes, _ = moe_route_held(
        jnp.asarray(logits), k, (0, e), scoring="sigmoid",
        bias=jnp.asarray(bias), n_group=n_group, topk_group=topk_group)
    free, *_ = moe_route_held(jnp.asarray(logits), k, (0, e),
                              scoring="sigmoid", bias=jnp.asarray(bias))
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    ref = np.asarray(R.route(dict(CFG, n_routed_experts=e,
                                  expert_share={"rank": 0, "of": 1}),
                             jnp.eye(e), jnp.asarray(bias),
                             jnp.asarray(logits)))
    left = 0
    for t in range(n):
        chosen, weights = _literal_route(scores[t], bias, n_group,
                                         topk_group, k)
        assert sorted(np.asarray(topi[t]).tolist()) == sorted(chosen)
        assert len({c // 8 for c in chosen}) <= topk_group
        by = dict(zip(np.asarray(topi[t]).tolist(),
                      np.asarray(gates[t]).tolist()))
        np.testing.assert_allclose([by[c] for c in chosen], weights,
                                   rtol=1e-5)
        np.testing.assert_allclose(ref[t][chosen], 2.5 * np.asarray(weights),
                                   rtol=1e-5)
        assert np.count_nonzero(ref[t]) == k
        left += set(np.asarray(free[t]).tolist()) != set(chosen)
    assert {16, 24} <= set(np.asarray(free[0]).tolist())
    assert not {16, 24} & set(np.asarray(topi[0]).tolist())
    assert left > n // 4


def layer_leaves(layer, kind, cfg=CFG):
    """(the reference's float32 leaves of one layer, the program's)."""
    stored = W.make_layer(W.seed_key(SEED), cfg, layer, kind, jnp.bfloat16)
    ref = {k: v.astype(jnp.float32) for k, v in stored.items()}
    prog = {k: v for k, v in ref.items() if k != "w_ukv"}
    prog.update(W.GW.split_ukv(W.glm_view(cfg), ref["w_ukv"]))
    return ref, prog


def share_case():
    """The share test: the routed parts that all 8 shares of the debug
    model give for one expert layer (a share is half a group), plus the
    shared expert once, add up to what the uncut reference gives for the
    whole layer, and no share alone does."""
    layer = 2
    x = jax.random.normal(jax.random.key(3), (24, CFG["hidden_size"]))
    uncut = dict(CFG, n_routed_experts=32, expert_share={"rank": 0, "of": 1})
    ref, _ = layer_leaves(layer, "moe", uncut)
    n = R._rms(x, ref["post_ln"], CFG["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R._experts(uncut, ref, ref, n, "float32"))
        shared = np.asarray(R.GR._swiglu(n, ref["ws_gate"], ref["ws_up"],
                                         ref["ws_down"]))
    _, uncut_prog = layer_leaves(layer, "moe", uncut)
    parts, groups = [], set()
    for rank in range(8):
        cfg = dict(CFG, expert_share={"rank": rank, "of": 8})
        mcfg = deepseek_program.deepseek_config(cfg, dtype="float32")
        assert mcfg.held_experts == (4 * rank, 4) and mcfg.n_group == 4
        # a share's leaves: an expert's draw depends on its id among all
        # the router's alone (benchmark/tests/test_deepseek.py), so they
        # are the uncut layer's with the share's four experts
        held = {k: v[4 * rank:4 * rank + 4] if k.startswith("we_") else v
                for k, v in uncut_prog.items()}
        # the one stack of all layers' held experts, this layer second
        w = {k: jnp.concatenate([jnp.zeros_like(held[k]), held[k]])
             for k in ("we_gate", "we_up", "we_down")}
        out, counts = jax.jit(lambda w, held: G._ffn(
            mcfg, w, held, "moe", 1, x, jnp.ones((24,), bool),
            jnp.zeros((5,), jnp.int32)))(w, held)
        parts.append(np.asarray(out - x) - shared)
        assert 0 <= int(counts[0]) < 4 * 24 and int(counts[1]) <= 4
        groups.add(int(counts[3]))
    assert np.abs(whole - shared).max() > 0.003
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-6)
    assert all(np.abs(p + shared - whole).max() > 3e-4 for p in parts)
    assert groups == {4}        # 24 tokens between them visit every group


# sha256 of the StableHLO that glm_moe_dsa's two paged programs lower to
# on its debug model, and moe_route_held without groups at the expert
# cells' router (mimo_v2's and glm_moe_dsa's route), taken at the parent
# of the PR that moved their shared code under this family; the two
# programs taken again where the device counters gained their fourth
# entry, the stream's rows (by operation and type the parent's text but
# for the vector's length, that entry's constant and its place in the
# sum: 59b4df8933ac561c and fee0de2eae3fe4f9 before)
PINNED = {"prefill_paged": "b2db1d487bac8ee6",
          "decode_chunk_paged": "bc36a82a64f17437",
          "moe_route_held": "2ae2a8358eb6b7e4"}


def pinned_case():
    import paddle_tpu as paddle
    paddle.seed(0)
    m = G.GlmMoeDsaForCausalLM("debug")
    m.eval()
    eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4, block_size=8,
                       prefix_cache=False)
    st, embed, fnorm, lm = eng._weights()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    texts = {
        "prefill_paged": eng._prefill.lower(
            st, embed, fnorm, lm, eng._scales, i32(1, 64), i32(1),
            i32(eng._max_blocks), i32(), *eng._pool()).as_text(),
        "decode_chunk_paged": eng._decode.lower(
            st, embed, fnorm, lm, eng._scales, i32(2),
            i32(2, eng._max_blocks), i32(2), *eng._pool()).as_text(),
        "moe_route_held": jax.jit(
            lambda lg, b, r: moe_route_held(
                lg, 8, (0, 16), scoring="sigmoid", bias=b, rows=r)[:4]
        ).lower(jnp.zeros((32, 256)), jnp.zeros((256,)),
                jnp.ones((32,), bool)).as_text()}
    assert len(eng._pool()) == 3 and eng._vp is not None
    for name, text in texts.items():
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == PINNED[name], name


# ... and this family's own two on its ``debug`` preset, taken at the
# parent of the PR that moved the block walk, the run scan, the greedy
# chunk and the held experts' FFN out of the family modules
OWN_PINNED = {"prefill_paged": "f0eee3ff52d02da8",
              "decode_chunk_paged": "59490ba4ef010970"}


def own_pinned_case():
    m = D.DeepseekV3ForCausalLM("debug")
    m.eval()
    assert paged_program_hashes(m) == OWN_PINNED


def block_case():
    """A cold prefill at blocks of 512 rows against the same prompt at
    256: the logits and the one pool's pages."""
    cold_prefill_at_blocks(
        model(), functools.partial(G._prefill, attend=D._prefill_attend))


@pytest.mark.parametrize("case", [
    engine_case, logits_case, kernel_case, yarn_case, route_case, share_case,
    pinned_case, own_pinned_case, block_case],
    ids=lambda f: f.__name__.removesuffix("_case"))
def test_deepseek_v3(case):
    case()


@pytest.mark.parametrize("start, pad, rows, keys", [
    # a right-aligned row: ``pad`` inside the second tile of 32 keys (the
    # first is skipped), ``first`` = 2, the block in the window's middle
    (48, 37, 64, 32),
    # the first block of such a row: queries ahead of ``pad`` see nothing
    (32, 37, 64, 32),
    # the last block of the window over all of it, one tile a block
    (80, 0, 64, 16),
    # tiles of 48 keys divide no ``start`` of this block or the next
    (32, 5, 64, 48), (64, 5, 64, 48),
    # 4 heads in programs of two and of one
    (64, 21, 32, 32), (80, 3, 16, 96)],
    ids=["pad_inside_a_tile", "first_block", "last_block", "tile_48_at_32",
         "tile_48_at_64", "two_heads_a_program", "one_head_a_program"])
def test_prefill_kernel_against_the_plain_pass(start, pad, rows, keys):
    got, want, _ = latent_prefill_against_plain(start, pad, rows, keys)
    width = np.abs(want).max()
    assert width > 1
    np.testing.assert_allclose(got, want, atol=1e-4 * width)
    # a query ahead of the row's first token sees nothing and adds nothing
    ahead = np.arange(start, start + 16) < pad
    assert not got[ahead].any() and got[~ahead].any(axis=(1, 2)).all()


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("chunked_prefill", {"chunked_prefill": True}),
    ("spec_decode", {"spec_decode": True}),
    ("kv_dtype='int8'", {"kv_dtype": "int8"}),
    ("paged=False", {"paged": False})])
def test_what_the_family_cannot_serve_raises_at_construction(option, kw):
    with pytest.raises(ValueError) as err:
        DecodeEngine(model(), **{**ENGINE, **kw})
    assert option in str(err.value) and "DeepseekV3ForCausalLM" \
        in str(err.value)


@pytest.mark.parametrize("key, value", [
    ("scoring_func", "softmax"), ("rope_type", "default"),
    ("topk_group", 5), ("mscale", 0.707)])
def test_what_no_published_configuration_sets_is_refused(key, value):
    with pytest.raises(ValueError):
        D.DeepseekV3Config(**{**D.DEEPSEEK_V3_PRESETS["debug"], key: value})

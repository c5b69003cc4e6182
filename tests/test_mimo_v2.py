"""The MiMo-V2 family through the paged engine, on the CPU at debug
widths (hidden 64, layers 0-6 of the published pattern, 16 experts of
which 4 are held, top 2, heads of 24 / 16 with 8 rotary, window 8):
the system against the benchmark's plain reference
(``benchmark/lib/mimo_reference.py``: full masks, a dense sum over the
held experts, no ring, no pages, no grouped product) on weights from a
seed; the share of the experts against the uncut layer; the router's
selection bias, the window and its sink; the ring a cold prefill leaves;
what the engine refuses for such a model; and that the other two
families' engine programs lower to what they did."""

import hashlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import mimo_program, mimo_reference  # noqa: E402
from benchmark.lib import mimo_weights as W  # noqa: E402
from harness import (cold_prefill_at_blocks, drive,  # noqa: E402
                     paged_program_hashes)
from paddle_tpu.distributed.fleet.moe import moe_route_held  # noqa: E402
from paddle_tpu.inference.serving import DecodeEngine  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import mimo_v2 as M  # noqa: E402

# the reference pads a sequence to shapes it compiles once; the cell's
# are 1024 tokens and 256 queries, these tests' sequences are under 64
mimo_reference.SEQ_BUCKET, mimo_reference.Q_BLOCK = 64, 32

SEED = 5
CFG = dict(
    name="debug-mimo", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=7, num_attention_heads=4,
    num_key_value_heads=1, swa_num_key_value_heads=2, head_dim=24,
    v_head_dim=16, vocab_size=256, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], n_routed_experts=4,
    expert_share={"rank": 0, "of": 4}, num_experts_per_tok=2,
    scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=None,
    partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
    sliding_window=8, attention_value_scale=0.707, layernorm_epsilon=1e-5,
    program={"model": {"dtype": "bfloat16"}})
# one engine shape for every case, so that its two programs compile once
ENGINE = dict(capacity=2, s_max=64, chunk=4, block_size=8, n_blocks=11,
              prefix_cache=False)
_MODEL = []


def model():
    """The builder's model on the seeded bfloat16 leaves, computing in
    float32: what the float32 reference reads, value for value."""
    if not _MODEL:
        m = mimo_program.build_model(CFG, SEED)
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
    return float(mimo_reference.served_gaps(
        SEED, CFG, seq, n_prompt)["served"].max())


_SERVED = []


def served():
    """One profiled engine that has served three requests (two rows of
    unequal length side by side, one of them past the window at once,
    then a third in the slot the first to retire gave up), for the
    cases that read it: (engine, its state arrays before the first
    launch, prompts, new tokens, requests)."""
    if not _SERVED:
        eng = DecodeEngine(model(), **ENGINE, profile=True)
        held = eng._state
        ps = prompts(21, 5, 9)
        news = (16, 9, 16)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, news)]
        drive(eng)
        _SERVED.append((eng, held, ps, news, reqs))
    return _SERVED[0]


def engine_case():
    """Prefill then 16 decoded tokens through ring, pool and experts
    against the plain reference. The counters only the device keeps
    reach ``stats()``."""
    eng, _, ps, news, reqs = served()
    for r, p in zip(reqs, ps):
        assert served_gap(r.wait(1), p.size) < 1e-6
    stats = eng.stats()
    assert stats["admitted"] == stats["retired"] == 3
    assert stats["state_slots_in_use"] == 0
    assert "ssm_row_steps" not in stats         # a recurrent family's own
    # every fed token meets 6 expert layers, each with 2 of 16 choices
    # of which 4 are held: pairs between none and all of them
    fed = sum(p.size + n - 1 for p, n in zip(ps, news))
    assert 0 < stats["moe_pairs"] <= 2 * 6 * fed
    assert 0 < stats["moe_expert_visits"] <= stats["moe_pairs"]
    assert stats["decode_ctx_tokens"] >= sum(
        sum(range(p.size, p.size + n - 1)) for p, n in zip(ps, news))
    snap = str(eng.metrics.snapshot())
    for name in ("engine_moe_pairs_total", "engine_moe_expert_visits_total",
                 "engine_decode_ctx_tokens_total",
                 "engine_state_slots_in_use"):
        assert name in snap
    # a window layer's slot holds sliding_window tokens, no more
    assert eng._state[0].shape == (5, 2, 2, 8, 24)
    assert eng._state[1].shape == (5, 2, 2, 8, 16)
    assert eng._kp.shape[0] == eng._vp.shape[0] == 2        # global layers
    assert (eng._kp.shape[-1], eng._vp.shape[-1]) == (128, 16)


def stats_case():
    """``stats()`` may be called from another thread than the one that
    steps the engine, while a launch holds the slot state donated: the
    counters' vector goes through the programs like the state but is
    never donated, so the one a launch returned stays readable. With
    ``profile`` on, ``stats()`` says what each launch was handed."""
    eng, held, *_ = served()
    assert held[0].is_deleted() and not held[-1].is_deleted()
    assert int(np.asarray(held[-1]).sum()) == 0
    stats = eng.stats()
    log = stats["launches"]     # [t, kind, units, rows, tokens, *counters]
    assert [e[2] for e in log if e[1] == "prefill"] == [1, 1, 1]
    assert sorted(e[4] for e in log if e[1] == "prefill") == [5, 9, 21]
    decode = [e for e in log if e[1] == "decode"]
    assert sum(e[4] for e in decode) == stats["decode_ctx_tokens"]
    assert sum(e[2] * e[3] for e in decode) == stats["decode_row_steps"]
    assert log[-1][5:] == [stats["moe_pairs"], stats["moe_expert_visits"],
                           stats["moe_full_stream"], stats["moe_stream_rows"]]
    # every (step or block, expert layer) ran the whole stream of its rows
    assert stats["moe_stream_rows"] >= stats["moe_pairs"] > 0
    assert all(a[0] <= b[0] and a[5] < b[5] for a, b in zip(log, log[1:]))


def ring_case():
    """The ring after a cold prefill of a prompt that is no multiple of
    the block or of the window holds what the decode steps would have
    left there: 13 prompt tokens and 8 decoded, against the 21 prefilled
    at once into the same slot."""
    eng = DecodeEngine(model(), **ENGINE)
    prompt, = prompts(13, seed=7)
    r = eng.submit(prompt, max_new_tokens=9)
    drive(eng)
    seq = np.asarray(r.wait(1))
    by_decode = [np.asarray(a) for a in eng._state[:2]]
    r = eng.submit(seq[:21], max_new_tokens=1)
    eng.admit([])               # the prefill alone: no decode step yet
    by_prefill = [np.asarray(a) for a in eng._state[:2]]
    drive(eng)
    assert np.asarray(r.wait(1))[-1] == seq[21]
    for a, b in zip(by_decode, by_prefill):
        assert np.abs(a).max() > 0.01
        np.testing.assert_allclose(b, a, atol=2e-6)


def share_case():
    """The share test: the parts that all 4 shares of the debug model
    give for one expert layer add up to what the uncut reference gives
    for the whole layer, and no share alone does."""
    layer, kind = 2, ("window", "moe")
    key = W.seed_key(SEED)
    x = jax.random.normal(jax.random.key(3), (24, CFG["hidden_size"]))
    uncut = dict(CFG, n_routed_experts=16, expert_share={"rank": 0, "of": 1})
    lp = {k: v.astype(jnp.float32) for k, v in W.make_layer(
        key, uncut, layer, kind, jnp.bfloat16).items()}
    n = mimo_reference._rms(x, lp["post_ln"], CFG["layernorm_epsilon"])
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(mimo_reference._experts(uncut, lp, n))
    parts = []
    for rank in range(4):
        cfg = dict(CFG, expert_share={"rank": rank, "of": 4})
        mcfg = mimo_program.mimo_config(cfg, dtype="float32")
        assert mcfg.held_experts == (4 * rank, 4)
        held = {k: v.astype(jnp.float32) for k, v in W.make_layer(
            key, cfg, layer, kind, jnp.bfloat16).items()}
        # the one stack of all layers' held experts, this layer third
        w = {k: jnp.concatenate([jnp.zeros_like(held[k])] * 2 + [held[k]])
             for k in ("we_gate", "we_up", "we_down")}
        out, counts = M._ffn(mcfg, w, held, "moe", 2, x,
                             jnp.ones((24,), bool), jnp.zeros((4,), jnp.int32))
        parts.append(np.asarray(out - x))
        assert 0 < int(counts[0]) < 2 * 24 and 0 < int(counts[1]) <= 4
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6)
    assert all(np.abs(p - whole).max() > 1e-3 for p in parts)


def router_case():
    """The selection bias changes which experts are chosen and not the
    weights: a weight is the expert's own score over the chosen scores'
    sum, whatever the bias that chose them; the pairs of experts that
    are not held carry weight 0 and sort behind the held ones."""
    logits = jax.random.normal(jax.random.key(1), (64, 16)) * 2.0
    bias = jax.random.uniform(jax.random.key(2), (16,), minval=-0.5,
                              maxval=0.5)
    plain = moe_route_held(logits, 2, (4, 4), scoring="sigmoid")
    topi, gates, order, sizes, _ = moe_route_held(
        logits, 2, (4, 4), scoring="sigmoid", bias=bias)
    assert (np.sort(topi, -1) != np.sort(plain[0], -1)).any(-1).mean() > 0.1
    scores = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(scores, np.asarray(topi), -1)
    mine = (np.asarray(topi) >= 4) & (np.asarray(topi) < 8)
    np.testing.assert_allclose(
        gates, np.where(mine, picked / picked.sum(-1, keepdims=True), 0),
        rtol=1e-6)
    assert int(sizes.sum()) == mine.sum()
    expert = np.asarray(topi).reshape(-1)[np.asarray(order)]
    head = expert[:mine.sum()]
    assert ((head >= 4) & (head < 8)).all() and (np.diff(head) >= 0).all()
    np.testing.assert_array_equal(np.bincount(head - 4, minlength=4), sizes)
    # rows that are no tokens are routed nowhere
    none = moe_route_held(logits, 2, (4, 4), scoring="sigmoid", bias=bias,
                          rows=jnp.zeros((64,), bool))
    assert int(none[3].sum()) == 0 and float(none[1].max()) == 0.0
    # the defaults are the dropless softmax route
    from paddle_tpu.distributed.fleet.moe import moe_route_dropless
    old = moe_route_dropless(logits, 16, 2)
    new = moe_route_held(logits, 2)
    for a, b in zip(old[:4], new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def window_case():
    """A window layer ignores a key ``sliding_window`` back and hears
    the one after it; the sink lowers every probability and gives no
    value."""
    cfg = model().config
    win, blk = cfg.sliding_window, 16
    ks = jax.random.split(jax.random.key(9), 6)
    q = jax.random.normal(ks[0], (blk, 4, 24))
    k = jax.random.normal(ks[1], (blk, 2, 24))
    v = jax.random.normal(ks[2], (blk, 2, 16))
    pk = jax.random.normal(ks[3], (win, 2, 24))
    pv = jax.random.normal(ks[4], (win, 2, 16))
    lp = {"sink": jnp.zeros((4,)), "wo": jnp.eye(64)}
    run = lambda k, pk: np.asarray(M._window_block(
        cfg, lp, q, k, v, pk, pv, start=32, pad=0)[0])
    base = run(k, pk)
    moved = run(k.at[3].add(1.0), pk)       # block row 3: column 35
    changed = np.abs(moved - base).max(-1) > 1e-6
    assert changed[3:3 + win].all() and not changed[:3].any() \
        and not changed[3 + win:].any()
    moved = run(k, pk.at[0].add(1.0))       # column 24: nobody's window
    np.testing.assert_array_equal(moved, base)
    assert (np.abs(run(k, pk.at[1].add(1.0)) - base).max(-1) > 1e-6
            ).tolist() == [True] + [False] * (blk - 1)
    s = jax.random.normal(ks[5], (2, 2, 5, 7))
    ok = jnp.ones((1, 1, 5, 7), bool)
    with_sink = M._softmax_with_sink(s, ok, jnp.zeros((2, 2, 1)))
    without = jax.nn.softmax(s, axis=-1)
    assert (np.asarray(with_sink) < np.asarray(without)).all()
    np.testing.assert_allclose(
        with_sink.sum(-1) + 1.0 / (1.0 + jnp.exp(s).sum(-1)), 1.0, rtol=1e-6)


def kernel_case():
    """The decode kernel over pools whose key and value heads differ in
    width (256 / 128, the pool's form of heads of 192 / 128), against
    its XLA reference, in interpret mode."""
    ks = jax.random.split(jax.random.key(4), 3)
    b, kvh, g, bs, n_pages = 3, 2, 4, 8, 12
    q = jax.random.normal(ks[0], (b, kvh, g, 256), jnp.float32)
    kp = jax.random.normal(ks[1], (2, n_pages, kvh, bs, 256), jnp.float32)
    vp = jax.random.normal(ks[2], (2, n_pages, kvh, bs, 128), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    lens = jnp.asarray([27, 9, 0], jnp.int32)
    got = pa.paged_attention_pallas(q, kp, vp, tables, lens, 1,
                                    interpret=True, name="paged_decode_qk192")
    want = pa._paged_attn_reference(q, kp, vp, tables, lens, 1)
    assert got.shape == (b, kvh, g, 128)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    assert float(jnp.abs(got[2]).max()) == 0.0          # a row with no page
    assert not pa._kernel_serves(kp.astype(jnp.int8), vp.astype(jnp.int8))


def block_case():
    """A cold prefill at blocks of 512 rows against the same prompt at
    256: the logits, the global layers' pages and the window layers'
    rings."""
    cold_prefill_at_blocks(
        model(), lambda cfg, *a: M._prefill(cfg, *a[:-2], jnp.int32(0),
                                            *a[-2:]))


# sha256 of the StableHLO this family's two paged programs lower to on
# its ``debug`` preset, taken at the parent of the PR that moved the
# block walk, the run scan, the greedy chunk and the held experts' FFN
# out of the family modules (models/paged_stack.py, fleet/moe.py)
PINNED = {"prefill_paged": "cfe714433bd8298b",
          "decode_chunk_paged": "9568685f6302c9a8"}


def pinned_case():
    m = M.MimoV2ForCausalLM("debug")
    m.eval()
    assert paged_program_hashes(m) == PINNED


@pytest.mark.parametrize("case", [
    engine_case, stats_case, ring_case, share_case, router_case,
    window_case, kernel_case, block_case, pinned_case],
    ids=lambda f: f.__name__)
def test_mimo_v2(case):
    case()


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


def _share_layer(n=256, d=64, f=32):
    """One expert layer at the routing shapes of the long_in cell (``n``
    rows, 8 of 256 experts a token, 16 held) and a small width: the
    second of two expert layers, so that the layer's groups lie behind
    another layer's in the one stack. (config, stack, layer leaves, x)."""
    cfg = M.MimoV2Config(
        vocab_size=256, hidden_size=d, intermediate_size=2 * d,
        moe_intermediate_size=f, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=1, swa_num_key_value_heads=2, head_dim=24,
        v_head_dim=16, hybrid_layer_pattern=(0, 1, 1),
        moe_layer_freq=(0, 1, 1), n_routed_experts=256,
        held_experts=(32, 16), num_experts_per_tok=8, sliding_window=8)
    ks = jax.random.split(jax.random.key(11), 6)
    w = {name: jax.random.normal(key, (32, *shape)) * 0.1
         for name, key, shape in (("we_gate", ks[0], (d, f)),
                                  ("we_up", ks[1], (d, f)),
                                  ("we_down", ks[2], (f, d)))}
    lp = {"post_ln": jnp.ones((d,)),
          "router": jax.random.normal(ks[3], (d, 256)) * 0.3,
          "router_bias": jnp.zeros((256,))}
    return cfg, w, lp, jax.random.normal(ks[4], (n, d))


def _dense_share(cfg, w, y, topi, gates, layer=1):
    """What the held experts of ``layer`` add for the normed tokens
    ``y``: every one of them computed for every token and weighted by
    the route (0 where not chosen)."""
    first, count = cfg.held_experts
    weight = (gates[..., None] * (topi[..., None] == first + jnp.arange(count))
              ).sum(1)                                          # [n, count]
    mine = slice(layer * count, (layer + 1) * count)
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", y, w["we_gate"][mine])) \
        * jnp.einsum("nd,edf->enf", y, w["we_up"][mine])
    return jnp.einsum("ne,enf,efd->nd", weight, h, w["we_down"][mine])


# the whole-stream program with every expert held, as the parent of the
# PR that brought the short stream lowered it (sha256 of the StableHLO)
WHOLE_STREAM_PINNED = "15c774aeb92595e3"
HELD = slice(32, 48)
# case -> (rows of the call, selection bias on which experts, rows of
# padding ahead, the router's (n_group, topk_group), the pairs it must
# bring (above, at most), the rows of the stream the products run over).
# 8 of 256 experts a token, 16 held: an even router brings rows / 2 pairs
STREAM_CASES = {
    # a decode chunk of 48 slots: P = 128 of 384 pairs, one rung of 128
    "48_rows_fit_the_rung": (48, None, 0, (1, 1), (8, 128), 128),
    "48_rows_overflow": (48, (slice(32, 40), 10.0), 0, (1, 1),
                         (383, 384), 384),
    # a block of 256 rows: P = 256 of 2048 pairs, rungs of 128 and 384
    "256_rows_fit_an_even_share": (256, None, 0, (1, 1), (64, 128), 128),
    "256_rows_between_the_rungs": (256, (HELD, 0.005), 0, (1, 1),
                                   (128, 256), 384),
    "256_rows_past_P_in_the_last_rung": (256, (HELD, 0.03), 0, (1, 1),
                                         (256, 384), 384),
    "256_rows_past_the_last_rung": (256, (HELD, 0.05), 0, (1, 1),
                                    (384, 640), 2048),
    "256_rows_overflow": (256, (slice(32, 40), 10.0), 0, (1, 1),
                          (2047, 2048), 2048),
    "256_rows_padded_first_block": (256, (HELD, 0.005), 100, (1, 1),
                                    (64, 128), 128),
    # a block of 512 rows: P = 512 of 4096 pairs, rungs of 384 and 640
    "512_rows_fit_the_first_rung": (512, None, 0, (1, 1), (128, 384), 384),
    "512_rows_between_the_rungs": (512, (HELD, 0.02), 0, (1, 1),
                                   (512, 640), 640),
    "512_rows_past_the_last_rung": (512, (HELD, 0.03), 0, (1, 1),
                                    (640, 1024), 4096),
    # ... under a router that keeps 4 of 8 groups of 32 experts (the
    # held 16 are half of the second group)
    "512_rows_grouped_first_rung": (512, None, 0, (8, 4), (128, 384), 384),
    "512_rows_grouped_between": (512, (HELD, 0.01), 0, (8, 4),
                                 (384, 640), 640),
}


@pytest.mark.parametrize("n_pairs, p, rungs", [
    (2048, None, ()), (64, 64, ()), (128, 128, ()), (384, 128, (128,)),
    (2048, 256, (128, 384)), (4096, 512, (384, 640)), (2048, 384, (384,)),
    (4096, 1024, (640, 1152))])
def test_the_short_streams_are_odd_numbers_of_tiles(n_pairs, p, rungs):
    """``moe_stream_rungs``: about P / 2 and P rows, each an odd number
    of the chip's tiles of 128 rows; none where P is over half of the
    stream (every expert held; a decode chunk of 8 or 16 slots)."""
    from paddle_tpu.distributed.fleet import moe
    assert moe.moe_stream_rungs(n_pairs, p) == rungs


@pytest.mark.parametrize("case", [*STREAM_CASES, "every_expert_held"])
def test_expert_products_over_the_head_of_the_stream(case):
    """``moe_dropless_ffn`` over the head of the expert-sorted order, as
    long as the first rung that holds the call's pairs, against the
    whole stream and against every held expert computed densely, where
    the rule engages: nothing is dropped when the pairs outgrow the last
    rung (every token favours held experts), and the device counters say
    when the whole stream ran and how many rows the products ran over."""
    from paddle_tpu.distributed.fleet import moe
    if case == "every_expert_held":
        cfg, w, lp, x = _share_layer()
        n = x.shape[0]
        topi, gates, order, sizes, rows_p = moe_route_held(
            x @ lp["router"][:, :16], 2)
        assert rows_p == n * 2 and moe.moe_stream_rungs(n * 2, rows_p) == () \
            and moe.moe_stream_rows(sizes, n * 2, rows_p) == n * 2
        args = (x, topi, gates, order, sizes,
                *(w[name][:16] for name in ("we_gate", "we_up", "we_down")))

        def moe_dropless_ffn(*a):
            return moe.moe_dropless_ffn(*a, stream_rows=rows_p)

        text = str(jax.make_jaxpr(moe_dropless_ffn)(*args))
        assert "cond" not in text and "while" not in text
        lowered = jax.jit(moe_dropless_ffn).lower(*args).as_text()
        assert hashlib.sha256(lowered.encode()).hexdigest()[:16] \
            == WHOLE_STREAM_PINNED
        return
    n, bias, pad, (n_group, topk_group), (above, at_most), ran = \
        STREAM_CASES[case]
    cfg, w, lp, x = _share_layer(n)
    k = cfg.num_experts_per_tok
    if bias is not None:
        lp["router_bias"] = lp["router_bias"].at[bias[0]].set(bias[1])
    rows = jnp.arange(n) >= pad
    y = M._rms(x, lp["post_ln"], cfg.layernorm_epsilon)
    topi, gates, order, sizes, rows_p = moe_route_held(
        y @ lp["router"], k, cfg.held_experts, scoring=cfg.scoring_func,
        bias=lp["router_bias"], rows=rows, n_group=n_group,
        topk_group=topk_group)
    assert rows_p == max(128, n) and order.shape == (n * k,)
    assert above < int(sizes.sum()) <= at_most
    assert int(moe.moe_stream_rows(sizes, n * k, rows_p)) == ran
    groups = jnp.zeros((32,), jnp.int32).at[16:].set(sizes)
    if n_group == 1:
        got, counts = jax.jit(
            lambda x, rows: M._ffn(cfg, w, lp, "moe", 1, x, rows,
                                   jnp.zeros((4,), jnp.int32)))(x, rows)
        assert counts.tolist() == [int(sizes.sum()), int((sizes > 0).sum()),
                                   int(ran == n * k), ran]
        got = got - x
    else:       # this family's router has no groups: the stream alone
        got = jax.jit(lambda y: moe.moe_dropless_ffn(
            y, topi, gates, order, groups, w["we_gate"], w["we_up"],
            w["we_down"], stream_rows=rows_p))(y)
    want = _dense_share(cfg, w, y, topi, gates)
    assert np.abs(want).max() > 0.01
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(got)[:pad].sum()) == 0.0
    # the same inputs through the whole stream
    whole = moe.moe_dropless_ffn(y, topi, gates, order, groups, w["we_gate"],
                                 w["we_up"], w["we_down"])
    np.testing.assert_allclose(got, whole, atol=2e-6)


# sha256 of the StableHLO granite_hybrid.py's two paged programs lower to
# on its debug model, taken at the parent of the PR that brought this
# family (llama.py's are pinned in tests/test_granite_hybrid.py)
GRANITE_PINNED = {"prefill_paged": "2a70ab005bb89721",
                  "decode_chunk_paged": "cf1cfd1d3feb7ff9"}


def test_granites_engine_programs_are_unchanged():
    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    paddle.seed(0)
    m = GraniteHybridForCausalLM("debug")
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
            i32(2, eng._max_blocks), i32(2), *eng._pool()).as_text()}
    assert eng._progs.device_counters == () and eng._hdv == eng._hd
    for name, text in texts.items():
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == GRANITE_PINNED[name], name

"""The Granite 4.0-H family through the paged engine, on the CPU at debug
widths: the system against the benchmark's plain reference
(``benchmark/lib/granite_reference.py``: a ``lax.scan`` over tokens, no
chunks, no cache, no kernels) on weights from a seed, the state every
slot holds beside its pages, what the engine refuses for such a model,
and that the seam left ``llama.py``'s engine programs as they were."""

import hashlib
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import granite_program, granite_reference  # noqa: E402
from harness import drive  # noqa: E402
from paddle_tpu.inference.serving import DecodeEngine  # noqa: E402
from paddle_tpu.kernels import ssm_update  # noqa: E402
from paddle_tpu.models import granite_hybrid as G  # noqa: E402

# the reference pads a sequence to a shape it compiles once: the cell's
# is 1024 tokens, and its scan over tokens walks the padding too; these
# tests' sequences are under 64 (the one that is not sets its own)
granite_reference.SEQ_BUCKET = 64

SEED = 5
CFG = dict(
    name="debug-granite", hidden_size=128, shared_intermediate_size=256,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=128,
    layer_types=["mamba", "mamba", "attention", "mamba", "attention"],
    mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8,
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.03125, logits_scaling=8, rms_norm_eps=1e-5,
    tie_word_embeddings=True, program={"model": {"dtype": "bfloat16"}})
ENGINE = dict(s_max=64, chunk=4, block_size=8, prefix_cache=False)
_MODEL = []


def model():
    """The builder's model on the seeded bfloat16 leaves, computing in
    float32: what the float32 reference reads, value for value."""
    if not _MODEL:
        m = granite_program.build_model(CFG, SEED)
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
    return float(granite_reference.served_gaps(
        SEED, CFG, seq, n_prompt)["served"].max())


def logits_case():
    """A length that is no multiple of the chunk of 8."""
    import paddle_tpu as paddle
    ids, = prompts(21)
    got = np.asarray(model().forward(paddle.to_tensor(ids[None]))._value)[0]
    want = np.asarray(granite_reference.logits_of(SEED, CFG, ids,
                                                  np.arange(ids.size)))
    assert np.abs(got - want).max() < 2e-6 * max(1.0, np.abs(want).max())
    assert want.std() > 1e-3


def engine_case():
    """Prefill then 16 decoded tokens through slot and pool: two rows of
    unequal length side by side, then a third in the slot the first to
    retire gave up."""
    eng = DecodeEngine(model(), capacity=2, **ENGINE)
    ps = prompts(21, 13, 9)
    news = (16, 9, 16)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, news)]
    drive(eng)
    for r, p in zip(reqs, ps):
        assert served_gap(r.wait(1), p.size) < 1e-6
    stats = eng.stats()
    assert stats["admitted"] == stats["retired"] == 3
    assert stats["state_slots_in_use"] == 0
    # rows a step: 2, then the third request beside what was left
    assert stats["ssm_row_steps"] >= sum(news) - 3
    assert stats["ssm_prefill_chunks"] == sum(
        -(-p.size // eng._prefill_block) * eng._progs.chunks_per_block
        for p in ps)
    snap = eng.metrics.snapshot()
    for name in ("engine_ssm_row_steps_total", "engine_state_slots_in_use",
                 "engine_ssm_prefill_chunks_total"):
        assert name in str(snap)


def preempt_case():
    """A row evicted for a higher priority resumes, by recomputing its
    prefill, to the tokens it would have served undisturbed."""
    low, high = prompts(20, 33, seed=3)
    calm = DecodeEngine(model(), capacity=2, **ENGINE)
    r = calm.submit(low, max_new_tokens=16)
    drive(calm)
    want = r.wait(1)
    eng = DecodeEngine(model(), capacity=2, n_blocks=7, **ENGINE)
    r_low = eng.submit(low, max_new_tokens=16)
    eng.admit([])
    eng.decode_once()
    r_high = eng.submit(high, max_new_tokens=8, priority=1)
    drive(eng)
    assert eng.stats()["preempted"] >= 1
    np.testing.assert_array_equal(r_low.wait(1), want)
    assert served_gap(r_high.wait(1), high.size) < 1e-6


@pytest.mark.parametrize("case", [logits_case, engine_case, preempt_case],
                         ids=lambda f: f.__name__)
def test_system_against_the_plain_reference(case):
    case()


def _tokenwise(cfg, lp, x):
    """The mixer's outputs and states by the one-token update, a token
    at a time from an empty slot."""
    pack = ssm_update.lane_pack(cfg.mamba_n_heads, cfg.mamba_d_head)
    ssm = jnp.zeros((1, 1, cfg.mamba_n_heads // pack, cfg.mamba_d_state,
                     pack * cfg.mamba_d_head), jnp.float32)
    conv = jnp.zeros((1, 1, cfg.mamba_d_conv - 1, cfg.conv_dim), x.dtype)
    outs = []
    for t in range(x.shape[0]):
        out, ssm, conv = G._mamba_decode(cfg, lp, x[t:t + 1], 0, ssm, conv,
                                         jnp.ones((1,), bool))
        outs.append(out[0])
    return jnp.stack(outs), ssm[0, 0], conv[0, 0]


def test_chunked_prefill_ends_in_the_state_decode_carries_on():
    """21 tokens in chunks of 8 (three pads ahead of them) against the
    token-by-token recurrence: outputs, final state, convolution
    window."""
    cfg = model().config
    lp = {n: model()._parameters[n]._value[0] for n in G._MAMBA}
    lp["input_ln"] = jnp.ones((cfg.hidden_size,), jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(
        21, cfg.hidden_size).astype(np.float32))
    pad = 3
    h = G._rms(jnp.concatenate([jnp.ones((pad, cfg.hidden_size)), x]),
               lp["input_ln"], cfg.rms_norm_eps)
    out, state, win = G._mamba_seq(
        cfg, lp, h,
        jnp.zeros((cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                  jnp.float32),
        jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_dim), jnp.float32),
        jnp.arange(pad + 21) >= pad)
    t_out, t_state, t_win = _tokenwise(cfg, lp, x)
    pack = ssm_update.lane_pack(cfg.mamba_n_heads, cfg.mamba_d_head)
    np.testing.assert_allclose(out[pad:], t_out, atol=2e-6)
    np.testing.assert_allclose(ssm_update.pack_state(state, pack), t_state,
                               atol=2e-6)
    np.testing.assert_allclose(win, t_win, atol=2e-6)


def test_a_state_perturbed_300_tokens_back_moves_the_logits():
    """The seeded decay is not trivial: what a slot's states held 300
    tokens ago still shows in the logits, and a broken cache cannot hide
    behind a state that forgets at once."""
    m = model()
    cfg = m.config
    w = {n: m._parameters[n]._value for n in m._stacked_names()}
    embed, fnorm = (m._parameters[n]._value
                    for n in ("embed_tokens", "final_norm"))
    progs = m.paged_programs(chunk=1, prefill_block=8)
    bs, n_tok = 8, 300
    mb = -(-(n_tok + 1) // bs)
    pool = (jnp.zeros((progs.kv_layers, mb + 1, progs.kv_heads, bs,
                       progs.head_dim), jnp.float32),) * 2 \
        + tuple(jnp.zeros(s.shape, s.dtype) for s in progs.slot_state(1))
    tables = jnp.arange(1, mb + 1, dtype=jnp.int32)[None]
    toks = jnp.asarray(np.random.RandomState(2).randint(
        1, cfg.vocab_size, (n_tok,)), jnp.int32)

    @jax.jit
    def last_logits(pool):
        def step(pool, xs):
            tok, t = xs
            # lens >= 1 marks the row live from its first token on
            logits, pool = G._decode_step(
                cfg, w, embed, fnorm, tok[None], tables, t[None] + 1, pool,
                jnp.ones((1,), bool))
            return pool, logits[0]
        _, logits = jax.lax.scan(step, pool,
                                 (toks, jnp.arange(n_tok, dtype=jnp.int32)))
        return logits[-1]

    kp, vp, ssm, conv = pool
    noise = jnp.asarray(np.random.RandomState(3).randn(*ssm.shape),
                        jnp.float32)
    base = last_logits(pool)
    moved = last_logits((kp, vp, ssm + noise, conv))
    assert float(jnp.abs(moved - base).max()) > 1e-3 * float(base.std())


@pytest.mark.parametrize("live", [(True, False, True, True, False, False),
                                  (False,) * 5 + (True,), (True,) * 6],
                         ids=["some", "last", "all"])
def test_the_update_kernel_touches_the_live_rows_only(live):
    """The Pallas kernel in interpret mode against the XLA reference and
    against the recurrence written out: y of the live rows, their
    states, and every other slot's state bit for bit as it was."""
    rng = np.random.RandomState(0)
    lm, b, h, p, n = 3, len(live), 8, 32, 16
    pack = ssm_update.lane_pack(h, p)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    state = f(lm, b, h, p, n)
    xs, dt, a, bm, cm = f(b, h, p), abs(f(b, h)), abs(f(b, h)), f(b, n), f(b, n)
    live = jnp.asarray(live)
    ssm = ssm_update.pack_state(state, pack)
    new = a[:, :, None, None] * state[1] \
        + (dt[:, :, None] * xs)[..., None] * bm[:, None, None, :]
    y = jnp.where(live[:, None, None],
                  (new * cm[:, None, None, :]).sum(-1), 0.0)
    want = ssm_update.pack_state(state.at[1].set(
        jnp.where(live[:, None, None, None], new, state[1])), pack)
    for fn, kw in ((ssm_update.ssm_update_reference, {}),
                   (ssm_update.ssm_update_pallas, {"interpret": True})):
        got, got_y = fn(ssm, 1, xs, dt, a, bm, cm, live, **kw)
        np.testing.assert_allclose(got_y, y, atol=1e-5)
        np.testing.assert_allclose(got, want, atol=1e-5)
        dead = ~np.asarray(live)
        np.testing.assert_array_equal(np.asarray(got)[:, dead],
                                      np.asarray(ssm)[:, dead])
        np.testing.assert_array_equal(np.asarray(got)[[0, 2]],
                                      np.asarray(ssm)[[0, 2]])


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("paged=False", dict(paged=False, prefix_cache=False)),
    ("chunked_prefill", dict(chunked_prefill=True, prefix_cache=False)),
    ("spec_decode", dict(spec_decode=True, prefix_cache=False)),
    ("kv_dtype='int8'", dict(kv_dtype="int8", block_size=32,
                             prefix_cache=False)),
    ("mesh", dict(mesh="a mesh", prefix_cache=False)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_the_family_cannot_serve_raises_at_construction(option, kw,
                                                             monkeypatch):
    if option == "mesh":
        from jax.sharding import Mesh
        kw = {**kw, "mesh": Mesh(np.asarray(jax.devices()[:1]), ("tp",))}
    with pytest.raises(ValueError, match="cannot be served with") as err:
        DecodeEngine(model(), **{"capacity": 2, "s_max": 64, **kw})
    assert option in str(err.value)


# sha256 of the StableHLO the engine's two paged programs lower to on
# the debug models. The decode pins were taken at the parent of the PR
# that moved the closures out of ``DecodeEngine._build`` into
# ``llama.py`` (``paged_programs``); the prefill pins moved once since,
# when the prefill wrote whole pages into donated pools (the text
# differs behind the block walk, in the write, and in the donation of
# the pool arguments, nowhere else). A change to what llama.py computes
# there moves them: lower the programs as below on the old and the new
# tree, see that the difference is the one meant, and put the new
# values here.
PINNED = {
    ("qwen2-debug", "fp", "prefill_paged"): "701f36ff285c2ede",
    ("qwen2-debug", "fp", "decode_chunk_paged"): "da0fd0f27c5f2c69",
    ("debug", "int8", "prefill_paged"): "9f36ceaf7e5bd9f1",
    ("debug", "int8", "decode_chunk_paged"): "3742e3fe503accac",
}


@pytest.mark.parametrize("preset, kv_dtype", [("qwen2-debug", "fp"),
                                              ("debug", "int8")])
def test_llamas_engine_programs_are_unchanged(preset, kv_dtype):
    from harness import shared_model
    eng = DecodeEngine(shared_model(preset), capacity=2, s_max=64, chunk=4,
                       block_size=8, kv_dtype=kv_dtype)
    st, embed, fnorm, lm = eng._weights()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    texts = {
        "prefill_paged": eng._prefill.lower(
            st, embed, fnorm, lm, eng._scales, i32(1, 64), i32(1),
            i32(eng._max_blocks), *eng._pool()).as_text(),
        "decode_chunk_paged": eng._decode.lower(
            st, embed, fnorm, lm, eng._scales, i32(2),
            i32(2, eng._max_blocks), i32(2), *eng._pool()).as_text()}
    assert eng._state == () and eng._progs.slot_state is None
    for name, text in texts.items():
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == PINNED[preset, kv_dtype, name], name

"""What the engine tests share, built in one place: the seeded read-only
model of a preset, the solo-``generate`` oracle, the loop that drives an
engine until it has served everything, and the clock every test runs
under (``conftest.py`` arms it)."""

import contextlib
import signal

import numpy as np
import pytest

import paddle_tpu as paddle

ENGINE_KW = dict(capacity=2, s_max=64, chunk=4, block_size=8)

_SHARED = {}   # preset -> (model, generator state right after its build)
_ORACLE = {}   # (preset, prompt, max_new) -> greedy tokens of a shared model


def fresh_model(preset="debug"):
    """``LlamaForCausalLM(preset)`` under ``paddle.seed(0)`` and
    ``eval()``: for a test that writes to its model (quantizing it,
    placing it on a mesh)."""
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(0)
    m = LlamaForCausalLM(preset)
    m.eval()
    return m


def shared_model(preset="debug"):
    """One :func:`fresh_model` per preset and process, for the tests that
    only read it: engines and ``generate`` do. Every call leaves the
    global generator where a fresh build would, so what a test draws
    afterwards does not depend on who built the model."""
    if preset not in _SHARED:
        _SHARED[preset] = (fresh_model(preset), paddle.get_rng_state())
    m, state = _SHARED[preset]
    paddle.set_rng_state(state)
    return m


def solo_generate(m, p, mn):
    """The oracle: ``m.generate`` alone on prompt ``p``, greedy, ``mn``
    new tokens. For a shared model the answer is kept, so the engine
    variants of one prompt set ask once."""
    def generate():
        return np.asarray(m.generate(
            paddle.to_tensor(p[None, :]), max_new_tokens=mn,
            temperature=0.0)._value)[0]

    preset = next((k for k, (shared, _) in _SHARED.items() if shared is m),
                  None)
    if preset is None:
        return generate()
    key = (preset, p.dtype.str, p.shape, p.tobytes(), mn)
    if key not in _ORACLE:
        _ORACLE[key] = generate()
    return _ORACLE[key].copy()


def drive(eng, pending=None, iters=2000):
    """Admit ``pending`` (a list, consumed in place) and step the engine
    until every request is served."""
    if pending is None:
        pending = []
    for _ in range(iters):
        eng.admit(pending)
        eng.decode_once()
        if eng.idle() and not pending:
            return
    raise AssertionError("engine did not drain the workload")


def drain(eng, reqs):
    """Serve what was submitted; the outputs of ``reqs`` in order."""
    drive(eng)
    return [np.asarray(r.wait(timeout=120)) for r in reqs]


def make_prompts(rng, vocab, sizes):
    return [rng.randint(1, vocab, (n,)).astype(np.int32) for n in sizes]


def run_engine(m, prompt_list, max_new=8, mesh=None, **kw):
    """A fresh four-slot paged engine serves ``prompt_list``; returns
    (outputs, engine)."""
    from paddle_tpu.inference.serving import DecodeEngine
    eng = DecodeEngine(m, capacity=4, s_max=64, chunk=4, block_size=8,
                       mesh=mesh, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompt_list]
    return drain(eng, reqs), eng


def latent_prefill_against_plain(start, pad, rows, keys, topk=None, heads=4,
                                 blk=16, total=96, seed=0):
    """The cold prefill's kernel (``kernels/latent_attention.py``,
    ``mla_latent_prefill``, here in interpret mode at ``rows`` rows a
    program and ``keys`` keys a fold) against
    the plain pass it stands for (``glm_moe_dsa._causal_latent_pass``'s
    body, what the CPU takes), float32: one block of ``blk`` queries at
    columns ``start..`` of a window of ``total`` whose column ``pad``
    holds position 0, layer 1 of 2, lanes past the latent zero; under
    ``topk`` each query sees its ``topk`` best of drawn scores alone.
    Returns (kernel's, plain pass's, the mask or None)."""
    from types import SimpleNamespace
    from unittest import mock

    import jax.numpy as jnp

    from paddle_tpu.kernels import latent_attention as LA
    from paddle_tpu.models import glm_moe_dsa as G
    rng = np.random.RandomState(seed)
    lanes, rank = 128, 64
    lat_c = rng.randn(2, total, lanes).astype(np.float32)
    qc = rng.randn(blk, heads, lanes).astype(np.float32)
    lat_c[..., 80:] = qc[..., 80:] = 0
    cfg = SimpleNamespace(kv_lora_rank=rank, logit_divisor=3.0)
    qcol = start + jnp.arange(blk)
    allowed = None
    if topk is not None:
        seen = G._block_seen(qcol, pad, 0, total)
        allowed = G._chosen_mask(
            jnp.where(seen, jnp.asarray(rng.randn(blk, total), jnp.float32),
                      -jnp.inf), topk)
    want = G._causal_latent_pass(cfg, jnp.asarray(qc), jnp.asarray(lat_c), 1,
                                 start, qcol, pad, pad // blk, allowed)
    with mock.patch.multiple(LA, _PREFILL_ROWS=rows, _PREFILL_KEYS=keys):
        assert LA._prefill_tiles(heads, blk, total) == (rows // blk, keys)
        got = LA.latent_prefill_pallas(
            jnp.asarray(qc), jnp.asarray(lat_c), 1, start, pad, allowed,
            rank=rank, scale=1 / 3.0, interpret=True)
    assert got.shape == want.shape == (blk, heads, rank)
    return np.asarray(got), np.asarray(want), allowed


def cold_prefill_at_blocks(model, run, tokens=696, s_max=1024):
    """One prompt of ``tokens`` (whole pages of 8) through a family's
    cold prefill at blocks of 256 and of 512 rows (its first block
    partly padding at either), the same pages handed to both: ``run(cfg, w, embed,
    final_norm, lm_head, ids, pad_len, table_row, pool, block)`` gives
    (float32 logits, pool). The logits agree, every page and whatever a
    slot holds is written alike, the device counters count the same
    pairs and the rows the stream ran over lie above them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import DecodeEngine
    eng = DecodeEngine(model, capacity=1, s_max=s_max, chunk=4, block_size=8,
                       prefix_cache=False)
    ids = np.zeros((1, s_max), np.int32)
    ids[0, s_max - tokens:] = np.random.RandomState(3).randint(
        1, eng._cfg.vocab_size, (tokens,))
    table_row = np.zeros((eng._max_blocks,), np.int32)
    table_row[:-(-tokens // 8)] = 1 + np.arange(-(-tokens // 8))
    got = {}
    for block in (256, 512):
        got[block] = jax.jit(lambda *pool: run(
            eng._cfg, *eng._weights(), ids,
            np.array([s_max - tokens], np.int32), table_row, pool, block)
        )(*eng._pool())
    (la, pa), (lb, pb) = got[256], got[512]
    assert float(jnp.abs(la).max()) > 0.01
    np.testing.assert_allclose(la, lb, atol=2e-5)
    for a, b in zip(pa[:-1], pb[:-1]):
        if a.shape[1] == eng.n_blocks:  # page 0 is what no row's table names
            a, b = a[:, 1:], b[:, 1:]
        assert float(jnp.abs(a).max()) > 0.01
        np.testing.assert_allclose(a, b, atol=2e-5)
    names = eng._progs.device_counters
    ca, cb = (dict(zip(names, np.asarray(p[-1]).tolist())) for p in (pa, pb))
    assert ca["moe_pairs"] == cb["moe_pairs"] > 0
    assert all(c["moe_stream_rows"] >= c["moe_pairs"] for c in (ca, cb))


def paged_program_hashes(model):
    """The first 16 digits of the sha256 of the StableHLO that a family's
    two paged programs lower to on ``model`` (a family with per-slot
    state, so its prefill is handed a ``slot``), in the engine shape the
    pinned programs of every family were taken at: program -> digits.
    ``as_text()`` carries no source locations, so code that moves
    between functions leaves them alone; what the program computes, and
    the order it is traced in, does not."""
    import hashlib
    import jax.numpy as jnp
    from paddle_tpu.inference.serving import DecodeEngine
    eng = DecodeEngine(model, **ENGINE_KW, prefix_cache=False)
    st, embed, fnorm, lm = eng._weights()
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    texts = {
        "prefill_paged": eng._prefill.lower(
            st, embed, fnorm, lm, eng._scales, i32(1, 64), i32(1),
            i32(eng._max_blocks), i32(), *eng._pool()).as_text(),
        "decode_chunk_paged": eng._decode.lower(
            st, embed, fnorm, lm, eng._scales, i32(2),
            i32(2, eng._max_blocks), i32(2), *eng._pool()).as_text()}
    return {name: hashlib.sha256(text.encode()).hexdigest()[:16]
            for name, text in texts.items()}


@contextlib.contextmanager
def per_test_clock(nodeid, limit_s):
    """Fail the test named ``nodeid`` when ``limit_s`` seconds pass inside
    the block (a hang becomes one failure, not a cut run). Main thread
    only; on exit the timer and handler found on entry are put back, so
    blocks nest."""
    def fire(signum, frame):
        pytest.fail(f"{nodeid} ran past its {limit_s} s limit",
                    pytrace=False)

    was_handler = signal.signal(signal.SIGALRM, fire)
    was_timer = signal.setitimer(signal.ITIMER_REAL, limit_s)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *was_timer)
        signal.signal(signal.SIGALRM, was_handler)


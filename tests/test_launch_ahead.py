"""The order of the serving loop's work (ISSUE 41): the engine hands the
chip its next paged program before it reads the last one back. A prefill
is launched and left unread, its first token stays in the device's token
vector, the decode chunk is launched behind it, and only then does the
host read, in launch order. What needs a token's value before it can
launch (drafting, the mixed step, a chunked prompt's last chunk) reads
first, and the server's loop no longer sleeps while a row is live.

Debug model, seconds in all."""

import queue
import time

import numpy as np
import pytest

from paddle_tpu.inference.serving import (BatchingServer, DecodeEngine,
                                          GenerationPredictor, _Request)
from paddle_tpu.observability import now

from harness import drive, shared_model, solo_generate

KW = dict(capacity=4, s_max=64, chunk=4, block_size=8)
MAX_NEW = 10


def _prompts(seed=0, sizes=(5, 9, 13, 7)):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 100, n).astype(np.int32) for n in sizes]


def _spy(eng):
    """Every launch of the two paged programs and every read-back, in
    the order the host made them: ``(what, time it returned)``."""
    log = []

    def wrap(name, fn):
        def spied(*a, **kw):
            out = fn(*a, **kw)
            log.append((name, now()))
            return out
        return spied

    eng._prefill = wrap("prefill", eng._prefill)
    eng._decode = wrap("decode", eng._decode)
    eng._fetch = wrap("read", eng._fetch)
    return log


class TestLaunchAhead:
    def test_three_prefills_and_the_chunk_before_the_first_read(self):
        m = shared_model()
        prompts = _prompts()
        eng = DecodeEngine(m, **KW)
        reqs = [_Request(p, MAX_NEW) for p in prompts]
        eng.admit([reqs[0]])
        eng.decode_once()
        log = _spy(eng)
        before = eng.stats()
        eng.admit(list(reqs[1:]))
        assert len(eng._unread) == 3        # launched, nothing read
        eng.decode_once()
        assert not eng._unread              # a step leaves none unread
        after = eng.stats()
        assert [w for w, _ in log] == ["prefill"] * 3 + ["decode"] \
            + ["read"] * 4
        assert after["launch_ahead"] - before["launch_ahead"] == 4
        assert after["settle_early"] - before["settle_early"] == 0
        drive(eng)
        # the same requests, served one at a time
        for r, p in zip(reqs, prompts):
            solo = DecodeEngine(m, **KW)
            one = _Request(p, MAX_NEW)
            drive(solo, [one])
            np.testing.assert_array_equal(np.asarray(one.wait(5)),
                                          np.asarray(r.wait(5)))

    def test_no_mark_before_its_token_is_on_the_host(self):
        eng = DecodeEngine(shared_model(), **KW)
        reqs = [_Request(p, MAX_NEW) for p in _prompts(1)]
        log = _spy(eng)
        eng.admit([reqs[0]])
        eng.decode_once()
        del log[:]
        eng.admit(list(reqs[1:]))
        eng.decode_once()
        reads = [t for w, t in log if w == "read"]
        assert len(reads) == 4              # three first tokens, the chunk
        for r, t_read in zip(reqs[1:], reads):
            first = r.trace.first("first_token")
            chunk = r.trace.first("decode_chunk")
            assert t_read <= first <= chunk
        # no decode_chunk mark of any row before the chunk's own read
        for r in reqs:
            assert all(t >= reads[3] for s, t in r.trace.events
                       if s == "decode_chunk" and t >= reads[0])
        drive(eng)

    def test_a_read_that_raises_fails_its_request_alone(self):
        m = shared_model()
        prompts = _prompts(2)
        eng = DecodeEngine(m, **KW)
        reqs = [_Request(p, MAX_NEW) for p in prompts]
        eng.admit([reqs[0]])
        eng.decode_once()
        fetch, n = eng._fetch, [0]

        def failing(arr):
            n[0] += 1
            if n[0] == 2:                   # the second prefill's token
                raise RuntimeError("injected: the read of a first token")
            return fetch(arr)

        eng._fetch = failing
        eng.admit(list(reqs[1:]))
        eng.decode_once()
        eng._fetch = fetch
        assert isinstance(reqs[2].error, RuntimeError)
        assert reqs[2].event.is_set()
        assert sum(r is not None for r in eng._rows) == 3
        drive(eng)
        for i in (0, 1, 3):                 # the rows launched beside it
            np.testing.assert_array_equal(
                np.asarray(reqs[i].wait(5)),
                solo_generate(m, prompts[i], MAX_NEW))
        s = eng.stats()
        assert s["failed"] == 1 and s["retired"] == 3
        # its pages went back: what is still in use is what the prefix
        # cache holds of the three that finished
        st = eng._alloc.stats()
        assert st["total_allocated"] - st["total_freed"] == st["used"]
        eng._cache.evict(st["used"])
        assert eng._alloc.stats()["used"] == 0

    @pytest.mark.parametrize("kw", [
        dict(spec_decode=True),
        dict(chunked_prefill=True),
        dict(spec_decode=True, mesh="tp2"),
    ], ids=["speculative", "chunked_prefill", "mixed"])
    def test_a_path_that_needs_token_values_settles_first(self, kw):
        m = shared_model()
        kw = dict(kw)
        if kw.get("mesh"):
            from paddle_tpu.inference.sharding import make_tp_mesh
            kw["mesh"] = make_tp_mesh(2)
        prompts = _prompts(3, sizes=(5, 19))
        eng = DecodeEngine(m, **{**KW, **kw})
        reqs = [_Request(p, MAX_NEW) for p in prompts]
        drive(eng, list(reqs))
        s = eng.stats()
        assert s["settle_early"] > 0
        for r, p in zip(reqs, prompts):
            np.testing.assert_array_equal(np.asarray(r.wait(5)),
                                          solo_generate(m, p, MAX_NEW))


class _SpyQueue(queue.Queue):
    """Records every ``get``: (timeout or None for no wait, whether the
    server had work at that moment)."""

    def __init__(self, server):
        super().__init__()
        self.server, self.gets = server, []

    def get(self, block=True, timeout=None):
        s = self.server
        busy = bool(s._pending) or not s.engine.idle()
        self.gets.append((timeout if block else None, busy))
        return super().get(block, timeout)


class TestPoll:
    def test_no_timed_get_while_a_row_is_live(self):
        server = BatchingServer(GenerationPredictor(shared_model()),
                                max_batch=2, continuous=True,
                                engine_kwargs=dict(s_max=64, chunk=4,
                                                   block_size=8))
        try:
            spy = server._q = _SpyQueue(server)
            time.sleep(0.12)                # the loop is on the spy now
            h = server.submit(_prompts(4)[0], max_new_tokens=24)
            h.wait(timeout=120)
            time.sleep(0.12)                # idle again
        finally:
            server.close()
        timed = [busy for t, busy in spy.gets if t is not None]
        assert timed and not any(timed)     # only the idle engine waits
        polls = [busy for t, busy in spy.gets if t is None]
        assert any(polls)                   # a live row: it just looks

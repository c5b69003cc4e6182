"""Chunked prefill (ISSUE 7): prompt prefill split into page-sized
chunks scheduled INTO decode steps under a per-step token budget.

Covers the StepBudget/plan_prefill scheduler contract, bit-identical
greedy outputs chunked-vs-monolithic-vs-solo (including preemption mid-
prefill and prefix-hit composition), lifecycle/metric accounting
(engine_prefill_chunks_total, prefill_chunk trace marks, first_token at
last-chunk completion, prefill-backlog gauge), and the compiled-shape
discipline: a mixed flood with the default page-sized chunk rides ONLY
the 16-slot prefix-prefill bucket — no third program shape."""

import math

import numpy as np
import pytest

from paddle_tpu.inference.scheduler import RequestScheduler, StepBudget

from harness import drive, shared_model, solo_generate


class _Req:
    """Bare scheduler item for StepBudget/plan_prefill unit tests."""

    def __init__(self, seq, priority=0):
        self._sched_seq = seq
        self.priority = priority


class TestStepBudget:
    def test_take_funds_whole_items_only(self):
        b = StepBudget(10)
        assert b.take(6) and b.used == 6 and b.remaining == 4
        assert not b.take(5)               # would overdraw: refused
        assert b.used == 6                 # refusal records nothing
        assert b.take(4) and b.remaining == 0

    def test_force_records_overdraft(self):
        """Decode lanes are never throttled — force=True always funds,
        and the spend still lands in ``used`` so the step histogram
        sees the real token load."""
        b = StepBudget(4)
        assert b.take(8, force=True)
        assert b.used == 8 and b.remaining == 0

    def test_zero_and_negative_are_free(self):
        b = StepBudget(0)
        assert b.take(0) and b.take(-3)
        assert b.used == 0

    def test_plan_prefill_stops_at_first_unaffordable(self):
        """Head-of-line order survives the budget: a later SMALL chunk
        must not overtake a starved earlier big one."""
        s = RequestScheduler()
        a, b, c = _Req(0), _Req(1), _Req(2)
        funded = s.plan_prefill(StepBudget(10), [(a, 8), (b, 8), (c, 1)])
        assert funded == [(a, 8)]          # b unaffordable, c NOT slid in

    def test_plan_prefill_priority_over_arrival(self):
        s = RequestScheduler()
        lo, hi = _Req(0, priority=0), _Req(1, priority=5)
        funded = s.plan_prefill(StepBudget(8), [(lo, 8), (hi, 8)])
        assert funded == [(hi, 8)]

    def test_fair_share_orders_by_vtime(self):
        """Under QoS, the tenant with the SMALLEST virtual time gets
        the next chunk — a long prompt's chunks rotate with other
        tenants' work instead of monopolising the budget."""
        from paddle_tpu.inference.qos import (FairShareScheduler,
                                              QoSPolicy, TenantPolicy)
        qos = QoSPolicy([TenantPolicy("a"), TenantPolicy("b")])
        s = FairShareScheduler(qos)
        ra, rb = _Req(0), _Req(1)
        ra.tenant, rb.tenant = "a", "b"
        s.charge("a", 100)                 # a already consumed a lot
        funded = s.plan_prefill(StepBudget(8), [(ra, 8), (rb, 8)])
        assert funded == [(rb, 8)]


class TestChunkedEngine:
    def test_requires_paged(self):
        from paddle_tpu.inference.serving import DecodeEngine
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(shared_model(), capacity=2, s_max=64, chunk=4,
                         paged=False, chunked_prefill=True)

    def test_bit_identical_vs_monolithic_and_solo(self):
        """The correctness oracle: same engine config, admission
        prefill vs chunked prefill, greedy outputs bit-identical (and
        both match solo generate)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(21)
        # mixed short/long: single-chunk, multi-chunk, and a prompt
        # whose final chunk is partial
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (8, 37, 7, 29)]
        solo = [solo_generate(m, p, 8) for p in prompts]

        def run(**kw):
            eng = DecodeEngine(m, capacity=4, s_max=96, chunk=4,
                               block_size=16, **kw)
            reqs = [_Request(p, 8) for p in prompts]
            drive(eng, list(reqs))
            return eng, [r.wait(timeout=1) for r in reqs]

        mono_eng, mono = run()
        ch_eng, ch = run(chunked_prefill=True)
        for c, a, s in zip(ch, mono, solo):
            np.testing.assert_array_equal(c, a)
            np.testing.assert_array_equal(c, s)
        # chunk accounting: one chunk per page-sized window of prompt
        want = sum(math.ceil(p.size / 16) for p in prompts)
        assert ch_eng.stats()["prefill_chunks"] == want
        assert mono_eng.stats().get("prefill_chunks", 0) == 0
        # prefill COMPLETIONS match the monolithic count 1:1
        assert ch_eng.prefills == mono_eng.prefills == len(prompts)

    def test_trace_marks_and_first_token_at_last_chunk(self):
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(22)
        p = rng.randint(1, 128, (37,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                           block_size=16, chunked_prefill=True)
        r = _Request(p, 6)
        drive(eng, [r])
        tr = r.trace
        assert tr.count("prefill_chunk") == math.ceil(p.size / 16)
        # TTFT spans admission -> LAST chunk's first token
        assert tr.first("first_token") >= tr.last("prefill_chunk")
        assert tr.ttft is not None and tr.is_complete()

    def test_step_budget_one_chunk_per_step(self):
        """step_budget small enough for one chunk per step: the prompt
        takes ceil(n/chunk) decode steps to become resident, and the
        budget histogram records every step's spend."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(23)
        p = rng.randint(1, 128, (40,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                           block_size=8, chunked_prefill=True,
                           step_budget=8)
        r = _Request(p, 4)
        eng.admit([r])
        row = next(x for x in eng._rows if x is not None)
        for step in range(1, 5):
            eng.decode_once()
            assert row["pf_pos"] == 8 * step      # exactly one chunk
        h = eng.metrics.get("engine_step_budget_used")
        assert h.count >= 4
        drive(eng, [])
        np.testing.assert_array_equal(r.wait(timeout=1),
                                      solo_generate(m, p, 4))

    def test_prefill_backlog_gauge(self):
        """stats()/gauge report queued prompt tokens not yet prefilled:
        scheduler backlog + in-flight rows' unprefilled remainders."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(24)
        p1 = rng.randint(1, 128, (24,)).astype(np.int32)
        p2 = rng.randint(1, 128, (16,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=1, s_max=96, chunk=4,
                           block_size=8, chunked_prefill=True,
                           step_budget=8)
        r1, r2 = _Request(p1, 4), _Request(p2, 4)
        eng.admit([r1, r2])                # r1 takes the slot, r2 queued
        assert eng.stats()["prefill_backlog"] == 40
        assert eng.metrics.get(
            "engine_prefill_backlog_tokens").value == 40
        eng.decode_once()                  # one 8-token chunk of r1
        assert eng.stats()["prefill_backlog"] == 32
        drive(eng, [])
        assert eng.stats()["prefill_backlog"] == 0
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      solo_generate(m, p1, 4))
        np.testing.assert_array_equal(r2.wait(timeout=1),
                                      solo_generate(m, p2, 4))

    def test_preempt_mid_prefill_resumes_losslessly(self):
        """A high-priority arrival evicts a row that is still MID
        chunked prefill; the victim resumes through re-admission (its
        completed pages may prefix-hit) and still bit-matches solo."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(25)
        p_lo = rng.randint(1, 128, (20,)).astype(np.int32)
        p_hi = rng.randint(1, 128, (17,)).astype(np.int32)
        solo_lo, solo_hi = solo_generate(m, p_lo, 4), solo_generate(m, p_hi, 4)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4,
                           chunked_prefill=True, step_budget=8)
        lo = _Request(p_lo, 4)
        eng.admit([lo])
        eng.decode_once()                  # lo mid-prefill: 8/20 tokens
        row = next(x for x in eng._rows if x is not None)
        assert "pf_seq" in row and row["pf_pos"] == 8
        hi = _Request(p_hi, 4, priority=5)
        pending = [hi]                     # needs all 3 usable pages
        drive(eng, pending)
        assert eng.stats()["preempted"] >= 1
        np.testing.assert_array_equal(hi.wait(timeout=1), solo_hi)
        np.testing.assert_array_equal(lo.wait(timeout=1), solo_lo)

    def test_preempt_after_first_token_resumes_with_tokens(self):
        """A chunked row preempted AFTER decode started resumes from
        its emitted tokens (the r7 recompute path), and first_token is
        marked exactly once across the stints."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(26)
        prompts = [rng.randint(1, 128, (7,)).astype(np.int32)
                   for _ in range(2)]
        solo = [solo_generate(m, p, 12) for p in prompts]
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4,
                           chunked_prefill=True)
        reqs = [_Request(p, 12) for p in prompts]
        drive(eng, list(reqs))
        assert eng.stats()["preempted"] >= 1
        for r, s in zip(reqs, solo):
            np.testing.assert_array_equal(r.wait(timeout=1), s)
            assert r.trace.count("first_token") == 1

    def test_grow_evicts_mid_prefill_row_no_livelock(self):
        """Tiny-pool regression: a decode-complete row needing ONE grow
        page with an equal-priority neighbor still mid-prefill must
        evict the prefilling row (least work lost, lossless resume) —
        not self-preempt into an admit→prefill→grow-fail cycle that
        starves the neighbor forever."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(30)
        # 6-tok retires early; 45-tok needs 6 prompt pages + 1 grow
        # page; 13-tok sits mid-prefill holding the last 2 pages
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (6, 45, 13, 31)]
        solo = [solo_generate(m, p, 10) for p in prompts]
        eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                           block_size=8, n_blocks=9,
                           chunked_prefill=True, step_budget=8)
        reqs = [_Request(p, 10) for p in prompts]
        drive(eng, list(reqs), iters=500)
        assert eng.stats()["preempted"] >= 1
        for r, s in zip(reqs, solo):
            np.testing.assert_array_equal(r.wait(timeout=1), s)

    def test_prefix_hit_composes_with_chunking(self):
        """A resubmitted shared prefix skips its cached pages: fewer
        chunks for the second request, outputs still bit-match solo."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(27)
        head = rng.randint(1, 128, (24,)).astype(np.int32)  # 3 pages
        p2 = np.concatenate([head, rng.randint(1, 128, (10,))
                             .astype(np.int32)])
        eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                           block_size=8, chunked_prefill=True)
        r1 = _Request(head, 4)
        drive(eng, [r1])
        cold_chunks = eng.stats()["prefill_chunks"]
        assert cold_chunks == 3
        r2 = _Request(p2, 4)
        drive(eng, [r2])
        warm_chunks = eng.stats()["prefill_chunks"] - cold_chunks
        # 34-token prompt cold would be 5 chunks; the 24-token prefix
        # is resident, so only the uncached tail is chunked
        assert warm_chunks < 5
        assert eng.metrics.get("engine_prefix_hit_tokens_total").value \
            >= 24
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      solo_generate(m, head, 4))
        np.testing.assert_array_equal(r2.wait(timeout=1),
                                      solo_generate(m, p2, 4))

    def test_qos_fair_share_bit_parity(self):
        """Chunked prefill under the fair-share scheduler: per-chunk
        charging reorders service but never corrupts it."""
        from paddle_tpu.inference.qos import QoSPolicy, TenantPolicy
        from paddle_tpu.inference.serving import DecodeEngine

        class _VClock:
            t = 0.0

            def __call__(self):
                return self.t

        m = shared_model()
        rng = np.random.RandomState(28)
        qos = QoSPolicy([TenantPolicy("h", weight=1.0),
                         TenantPolicy("l", weight=10.0)],
                        clock=_VClock())
        eng = DecodeEngine(m, capacity=2, s_max=96, chunk=4,
                           block_size=16, qos=qos, chunked_prefill=True)
        work = []
        for i in range(4):
            p = rng.randint(1, 128, (5 + 9 * i,)).astype(np.int32)
            work.append((p, eng.submit(p, max_new_tokens=5,
                                       tenant="h" if i % 2 else "l")))
        for _ in range(400):
            eng.admit([])
            eng.decode_once()
            if eng.idle() and not eng.backlog:
                break
        for p, r in work:
            np.testing.assert_array_equal(r.wait(timeout=1),
                                          solo_generate(m, p, 5))
        assert eng.stats()["prefill_chunks"] >= 4

    def test_no_new_compiled_program_shapes(self):
        """The shape-bucketing acceptance: a mixed flood with the
        default page-sized chunk rides ONLY the already-documented
        16-slot prefix-prefill bucket — no third program shape beyond
        the r7 bucket set, regardless of prompt length mix."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(29)
        prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
                   for n in (5, 18, 33, 60)]
        eng = DecodeEngine(m, capacity=4, s_max=96, chunk=4,
                           block_size=16, chunked_prefill=True)
        reqs = [_Request(p, 4) for p in prompts]
        drive(eng, list(reqs))
        for r in reqs:
            r.wait(timeout=1)
        # every chunk window bucketed to the one 16-slot program; the
        # full-window cold-prefill shape monolithic admission uses for
        # these prompts never compiled, and paged decode adds no
        # windowed shapes
        assert set(eng._prefix_progs) == {16}
        assert eng._decode_progs == {}
        # a non-default chunk size buckets to ITS one window — still a
        # member of the documented power-of-two set, still one shape
        eng32 = DecodeEngine(m, capacity=4, s_max=96, chunk=4,
                             block_size=16, chunked_prefill=True,
                             prefill_chunk=32)
        reqs = [_Request(p, 4) for p in prompts]
        drive(eng32, list(reqs))
        assert set(eng32._prefix_progs) <= {16, 32}

"""Prefix-sharing radix KV cache + preempting scheduler (ISSUE 2):
refcounted allocator semantics, radix insert/match/evict, the
no-page-aliased-by-two-writers ownership invariant (property-style
simulation of the engine's allocation protocol), scheduler ordering,
prefix-hit admission charging only the uncached suffix, and lossless
preemption round-trips (tiny pool bit-matches ample pool)."""

import numpy as np
import pytest

from paddle_tpu.inference.paged_cache import BlockAllocator
from paddle_tpu.inference.prefix_cache import PrefixCache
from paddle_tpu.inference.scheduler import RequestScheduler

from harness import drive, shared_model, solo_generate


class TestAllocatorRefcounts:
    def test_incref_decref_lifecycle(self):
        a = BlockAllocator(5)
        (p,) = a.allocate(1)
        assert a.refcount(p) == 1
        a.incref(p)
        assert a.refcount(p) == 2
        a.decref(p)
        assert a.refcount(p) == 1 and a.num_used == 1
        a.decref(p)                        # last reader frees
        assert a.refcount(p) == 0 and a.num_free == 4

    def test_ref_ops_on_unallocated_raise(self):
        a = BlockAllocator(5)
        with pytest.raises(ValueError):
            a.incref(1)
        with pytest.raises(ValueError):
            a.decref(1)

    def test_free_of_shared_page_raises(self):
        """A unilateral free of a page another reader still maps is the
        aliasing bug the refcount layer exists to prevent."""
        a = BlockAllocator(5)
        pages = a.allocate(2)
        a.incref(pages[0])
        with pytest.raises(ValueError, match="decref"):
            a.free(pages)
        a.decref(pages[0])
        a.free(pages)                      # exclusive again: fine
        assert a.num_used == 0

    def test_watermark_and_cumulative_counters(self):
        a = BlockAllocator(9)
        first = a.allocate(3)
        a.free(first)
        a.allocate(2)
        assert a.high_watermark == 3       # peak, not current
        assert a.total_allocated == 5      # cumulative, never decreases
        assert a.stats()["high_watermark"] == 3


class TestRadixTree:
    def _cache(self, n_blocks=17, bs=4):
        a = BlockAllocator(n_blocks)
        return a, PrefixCache(a, bs)

    def test_insert_then_full_match(self):
        a, c = self._cache()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        pages = a.allocate(2)
        assert c.insert(toks, pages) == 2
        m = c.match(toks, 8)
        assert m.pages == pages and m.cached_len == 8
        assert m.cow_src is None
        assert a.refcount(pages[0]) == 3   # row + cache + match
        c.release(m)
        assert a.refcount(pages[0]) == 2

    def test_partial_tail_is_cow_only(self):
        """A node shorter than block_size is never handed out shared —
        the matcher returns it as a COW source."""
        a, c = self._cache()
        pages = a.allocate(2)
        c.insert([1, 2, 3, 4, 5, 6], pages)    # full page + 2-token leaf
        m = c.match([1, 2, 3, 4, 5, 9], 6)
        assert m.pages == [pages[0]]
        assert m.cow_src == pages[1] and m.cow_len == 1
        assert m.cached_len == 5
        c.release(m)

    def test_limit_caps_the_match(self):
        """limit = ns-1 in the engine: the admitting row always keeps
        at least one real token to prefill, even on a full-prompt hit."""
        a, c = self._cache()
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        c.insert(toks, a.allocate(2))
        m = c.match(toks, 7)               # second full page blocked...
        assert len(m.pages) == 1
        assert m.cow_len == 3              # ...but COWs up to the cap
        assert m.cached_len == 7
        c.release(m)
        m = c.match(toks, 4)               # page-aligned cap: no COW
        assert len(m.pages) == 1 and m.cow_src is None
        c.release(m)

    def test_insert_is_first_wins(self):
        a, c = self._cache()
        toks = [1, 2, 3, 4]
        incumbent = a.allocate(1)
        c.insert(toks, incumbent)
        dup = a.allocate(1)
        assert c.insert(toks, dup) == 0    # duplicate adopts nothing
        assert a.refcount(dup[0]) == 1     # still only the caller's ref
        m = c.match(toks, 8)
        assert m.pages == incumbent
        c.release(m)

    def _publish(self, a, c, toks, n_pages):
        """The engine's retire shape: insert, then the row drops its
        own references (the cache's ref is what keeps pages alive)."""
        pages = a.allocate(n_pages)
        c.insert(toks, pages)
        for p in pages:
            a.decref(p)
        return pages

    def test_evict_lru_and_cascade(self):
        a, c = self._cache()
        self._publish(a, c, [1, 2, 3, 4, 5, 6, 7, 8], 2)
        self._publish(a, c, [9, 10, 11, 12], 1)
        c.release(c.match([1, 2, 3, 4, 5, 6, 7, 8], 8))   # touch all of
        used0 = a.num_used      # chain 1: chain 2 becomes the LRU victim
        assert c.evict(1) == 1
        m = c.match([9, 10, 11, 12], 4)
        assert m.cached_len == 0 and not m.pages
        # chain 1's leaf then its exposed parent go next (cascade)
        assert c.evict(2) == 2
        assert len(c) == 0
        assert a.num_used == used0 - 3

    def test_evict_never_touches_referenced_pages(self):
        a, c = self._cache()
        toks = [1, 2, 3, 4]
        self._publish(a, c, toks, 1)
        m = c.match(toks, 8)               # a live reader holds a ref
        assert c.evict(5) == 0
        c.release(m)
        assert c.evict(5) == 1             # reader gone: evictable


class TestOwnershipInvariant:
    def test_no_page_aliased_by_two_writers(self):
        """Property-style simulation of the engine's exact allocation
        protocol (match -> allocate -> adopt/COW -> insert -> decref)
        under a small token alphabet (to force heavy sharing): at every
        step, every page a live row may WRITE has refcount exactly 1,
        shared pages are never writable, and full teardown returns the
        pool to empty."""
        rng = np.random.RandomState(0)
        bs = 4
        a = BlockAllocator(41)
        c = PrefixCache(a, bs)
        writers: dict[int, int] = {}       # page -> owning row id
        live: dict[int, dict] = {}
        next_id = 0

        def check():
            for p, owner in writers.items():
                assert a.refcount(p) == 1, \
                    f"page {p} writable by row {owner} has readers"
            for row in live.values():
                for p in row["shared"]:
                    assert p not in writers
                    assert a.refcount(p) >= 2   # cache + this row

        for _ in range(300):
            if live and (rng.rand() < 0.4 or len(live) >= 6):
                rid = rng.choice(list(live))
                row = live.pop(rid)
                c.insert(row["seq"], row["shared"] + row["own"])
                for p in row["own"]:
                    del writers[p]         # published = read-only now
                for p in row["shared"] + row["own"]:
                    a.decref(p)
                check()
                continue
            seq = list(rng.randint(1, 5, rng.randint(2, 21)))
            ns = len(seq)
            m = c.match(seq, ns - 1)
            need = -(-ns // bs) - len(m.pages)
            pages = a.allocate(need)
            if pages is None:
                c.evict(need - a.num_free)
                pages = a.allocate(need)
            if pages is None:
                c.release(m)               # pool busy: skip this arrival
                continue
            for p in pages:
                assert a.refcount(p) == 1 and p not in writers
            if m.cow_src is not None:      # "device copy" then release
                assert a.refcount(m.cow_src) >= 2
                c.release_cow(m)
            rid, next_id = next_id, next_id + 1
            live[rid] = {"seq": seq, "shared": list(m.pages),
                         "own": list(pages)}
            for p in pages:
                writers[p] = rid
            check()
        for rid in list(live):
            row = live.pop(rid)
            for p in row["shared"] + row["own"]:
                a.decref(p)
        c.evict(a.capacity)
        assert a.num_used == 0 and a.num_free == a.capacity


class _Req:
    def __init__(self, priority=0):
        self.priority = priority


class TestRequestScheduler:
    def test_priority_then_fcfs(self):
        s = RequestScheduler()
        lo1, hi, lo2 = _Req(0), _Req(2), _Req(0)
        for r in (lo1, hi, lo2):
            s.add(r)
        assert s.peek() is hi              # peek does not remove
        assert len(s) == 3
        assert [s.pop() for _ in range(3)] == [hi, lo1, lo2]
        assert not s

    def test_requeue_keeps_original_arrival_order(self):
        """A preempted request re-enters at its ORIGINAL FCFS position
        among equal priorities — preemption must not cost it its turn."""
        s = RequestScheduler()
        r1, r2 = _Req(), _Req()
        s.add(r1)
        s.add(r2)
        assert s.pop() is r1               # admitted...
        r3 = _Req()
        s.add(r3)
        s.add(r1)                          # ...then preempted back in
        assert [s.pop() for _ in range(3)] == [r1, r2, r3]

    def test_drain_returns_queue_order(self):
        s = RequestScheduler()
        reqs = [_Req(p) for p in (0, 3, 1)]
        for r in reqs:
            s.add(r)
        assert s.drain() == [reqs[1], reqs[2], reqs[0]]
        assert len(s) == 0
        with pytest.raises(IndexError):
            s.pop()


class TestPrefixEngine:
    def test_resubmission_allocates_zero_prefix_pages(self):
        """The acceptance delta: an identical re-submission funds ZERO
        pages for the shared prefix — only the one tail page (the
        allocator's cumulative counter makes the charge observable)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(11)
        # 17 tokens / bs 8: two FULL shared pages + a 1-token tail;
        # 17 + 4 new stays inside 3 pages, so admission is the only
        # allocation and the charge is exact
        p = rng.randint(1, 128, (17,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8)
        r1 = _Request(p, 4)
        drive(eng, [r1])
        cold_delta = eng._alloc.total_allocated
        assert cold_delta == 3             # ceil(17/8), charged in full
        r2 = _Request(p, 4)
        drive(eng, [r2])
        warm_delta = eng._alloc.total_allocated - cold_delta
        assert warm_delta == 1             # tail page only: both shared
        #                                    prefix pages cost nothing
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      r2.wait(timeout=1))
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      solo_generate(m, p, 4))
        s = eng.stats()
        assert s["prefix_hit_tokens"] == 16
        assert s["admitted"] == 2 and s["retired"] == 2
        assert s["prefix_cache"]["hits"] == 1

    def test_shared_system_prompt_outputs_match_solo(self):
        """Mid-page sharing: requests repeat a 12-token system prompt
        (one full page + 4 COW tokens at bs 8) with distinct suffixes.
        Every warm admission runs the COW + position-offset tail
        prefill; greedy outputs must still bit-match solo generate."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(12)
        sys_p = rng.randint(1, 128, (12,)).astype(np.int32)
        prompts = [np.concatenate([sys_p, rng.randint(
            1, 128, (5,)).astype(np.int32)]) for _ in range(4)]
        solo = [solo_generate(m, p, 6) for p in prompts]
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8)
        reqs = []
        for p in prompts:                  # serial: each retire
            r = _Request(p, 6)             # publishes before the next
            drive(eng, [r])          # admission matches
            reqs.append(r)
        for r, s in zip(reqs, solo):
            np.testing.assert_array_equal(r.wait(timeout=1), s)
        st = eng.stats()
        assert st["prefix_hit_tokens"] > 0
        assert st["prefix_cache"]["hits"] >= 3

    def test_preemption_roundtrip_tiny_pool_matches_ample(self):
        """The lossless-preemption acceptance: a pool too small for two
        growing rows forces self-preemption + recompute-resume; greedy
        outputs must be bit-identical to an ample pool (and solo)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(13)
        prompts = [rng.randint(1, 128, (7,)).astype(np.int32)
                   for _ in range(2)]
        solo = [solo_generate(m, p, 12) for p in prompts]

        def run(**kw):
            eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                               block_size=8, **kw)
            reqs = [_Request(p, 12) for p in prompts]
            drive(eng, list(reqs))
            return eng, [r.wait(timeout=1) for r in reqs]

        # 3 usable pages; each row needs 3 to finish (7 + 12 - 1 = 18
        # tokens) — they cannot coexist, so one must round-trip through
        # preemption while the other runs the pool alone
        tiny_eng, tiny = run(n_blocks=4)
        ample_eng, ample = run()
        assert tiny_eng.stats()["preempted"] >= 1
        assert ample_eng.stats()["preempted"] == 0
        for t, a, s in zip(tiny, ample, solo):
            np.testing.assert_array_equal(t, a)
            np.testing.assert_array_equal(t, s)
        assert tiny_eng._alloc.num_used <= 3   # only cached pages remain

    def test_priority_admits_first_and_preempts_lower(self):
        """Priority beats arrival at admission, and a high-priority
        arrival evicts a strictly-lower running row when the pool can't
        fund it otherwise — the evicted row still finishes losslessly."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(14)
        p_lo = rng.randint(1, 128, (7,)).astype(np.int32)
        p_hi = rng.randint(1, 128, (17,)).astype(np.int32)
        solo_lo = solo_generate(m, p_lo, 12)
        solo_hi = solo_generate(m, p_hi, 4)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4)
        lo = _Request(p_lo, 12)
        eng.admit([lo])
        eng.decode_once()                  # lo is mid-generation...
        hi = _Request(p_hi, 4, priority=5)
        pending = [hi]                     # ...when hi needs all 3 pages
        drive(eng, pending)
        np.testing.assert_array_equal(hi.wait(timeout=1), solo_hi)
        np.testing.assert_array_equal(lo.wait(timeout=1), solo_lo)
        assert eng.stats()["preempted"] >= 1

    def test_equal_priority_never_preempted_at_admission(self):
        """Strictly-lower only: an equal-priority claimant WAITS for the
        running row instead of evicting it (no preemption cycles)."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(15)
        p1 = rng.randint(1, 128, (12,)).astype(np.int32)
        p2 = rng.randint(1, 128, (17,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4)
        r1 = _Request(p1, 4)
        eng.admit([r1])                    # holds 2 of 3 pages
        r2 = _Request(p2, 4)               # needs 3: must wait
        eng.admit([r2])
        assert eng.stats()["preempted"] == 0
        assert eng.backlog == 1 and not eng.idle()
        drive(eng, [])
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      solo_generate(m, p1, 4))
        np.testing.assert_array_equal(r2.wait(timeout=1),
                                      solo_generate(m, p2, 4))

    def test_infeasible_prompt_fails_loudly(self):
        """A prompt no amount of eviction/preemption can fund fails with
        the pool arithmetic in the message, not a silent hang."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(16)
        p = rng.randint(1, 128, (30,)).astype(np.int32)   # 4 pages
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, n_blocks=4)      # pool holds 3
        r = _Request(p, 4)
        eng.admit([r])
        with pytest.raises(RuntimeError, match="pool holds 3"):
            r.wait(timeout=1)
        assert eng.stats()["failed"] == 1
        assert eng.idle()                  # not parked in the backlog

    def test_prefix_cache_off_still_serves(self):
        """prefix_cache=False: no radix cache, no self-preemption — the
        r6 exhaustion behavior — but plain workloads are unchanged."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(17)
        p = rng.randint(1, 128, (9,)).astype(np.int32)
        eng = DecodeEngine(m, capacity=2, s_max=64, chunk=4,
                           block_size=8, prefix_cache=False)
        r1, r2 = _Request(p, 4), _Request(p, 4)
        drive(eng, [r1, r2])
        np.testing.assert_array_equal(r1.wait(timeout=1),
                                      r2.wait(timeout=1))
        s = eng.stats()
        assert "prefix_cache" not in s
        assert s["pool"]["used"] == 0      # nothing retained


@pytest.mark.slow
class TestPreemptionStress:
    def test_mixed_priority_starved_pool_all_bit_match_solo(self):
        """Sustained mixed-priority arrivals through a pool an order of
        magnitude too small for the aggregate demand: every request that
        completes must bit-match solo, nothing may hang, and the only
        allowed failures are explicit pool-infeasibility errors."""
        from paddle_tpu.inference.serving import DecodeEngine, _Request
        m = shared_model()
        rng = np.random.RandomState(18)
        eng = DecodeEngine(m, capacity=3, s_max=64, chunk=4,
                           block_size=8, n_blocks=6)
        reqs, solo = [], []
        for i in range(10):
            n = int(rng.randint(3, 14))
            mn = int(rng.choice([3, 6, 10]))
            p = rng.randint(1, 128, (n,)).astype(np.int32)
            reqs.append(_Request(p, mn, priority=int(rng.randint(0, 3))))
            solo.append(solo_generate(m, p, mn))
        queue = list(reqs)
        pending = []
        for _ in range(2000):
            while queue and len(pending) < 2:
                pending.append(queue.pop(0))
            eng.admit(pending)
            eng.decode_once()
            if not queue and not pending and eng.idle():
                break
        else:
            raise AssertionError("stress workload did not drain")
        for r, s in zip(reqs, solo):
            np.testing.assert_array_equal(r.wait(timeout=1), s)
        st = eng.stats()
        assert st["retired"] == 10 and st["failed"] == 0
        assert st["pool"]["high_watermark"] <= 5

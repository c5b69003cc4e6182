"""Serving path (SURVEY item 14 depth; reference:
paddle/fluid/inference/api/ AnalysisPredictor behind paddle_serving /
fastdeploy — request batching in front of a compiled predictor; LLM
serving rides masked_multihead_attention decode kernels).

TPU-native pieces:
- :class:`GenerationPredictor` — causal-LM serving over the KV-cache
  fused decode (models.llama _generate_cached): one compiled program per
  (batch, prompt_len, max_new) bucket, bf16 weight option, tokens/s
  accounting emitted to the structured event log.
- :class:`BatchingServer` — dynamic request batching: concurrent
  submit() calls coalesce into one padded batch per tick (the
  continuous-batching-lite pattern every serving stack fronts the
  predictor with), futures resolve per request.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time

import numpy as np

from ..observability import MetricsRegistry, RequestTrace, now as _now
from ..profiler import RecordEvent
from ..utils.log import get_logger, log_event, log_kv

__all__ = ["GenerationPredictor", "BatchingServer", "DecodeEngine"]

_log = get_logger("paddle_tpu.inference.engine")


class _NullSpan:
    """No-op phase guard: the ``profile=None`` hot path enters this
    singleton instead of a profiler span, so the cost of instrumentation
    with profiling off is one attribute check per phase."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOPROF = _NullSpan()
# The benchmark's readers take a launch's entry (``stats()["launches"]``)
# by position: the device counters behind ``tokens``, the host's behind
# them. A device counter the programs gained since rides at the entry's
# END, so that every older field keeps its place.
_LOG_LAST = ("moe_stream_rows",)


def _phase(prof, name, args=None):
    """Phase guard for ``with`` — a real profiler span when a
    StepProfiler is attached, the no-op singleton otherwise. ``args``
    (a launch's identity, ``DecodeEngine._launch_args``) go on the
    span's trace annotation."""
    return _NOPROF if prof is None else prof.phase(name, args)


def _tmark(req, state, worker=None, n_tokens=None):
    """Mark a lifecycle transition on the request's trace (requests
    without one — foreign test doubles — are silently skipped).
    ``worker`` attributes the event to a fleet worker lane (ISSUE 5);
    ``n_tokens`` annotates how many output tokens the event emitted
    (ISSUE 8: a speculative verify step emits 1..k+1 per mark)."""
    tr = getattr(req, "trace", None)
    return None if tr is None else tr.mark(state, worker=worker,
                                           n_tokens=n_tokens)


class DecodeEngine:
    """Continuous batching with a CARRIED KV cache (VERDICT r4 #5;
    reference: the fastdeploy/paddle-serving continuous-batching loop
    over masked_multihead_attention decode kernels).

    Default mode is PAGED (``paged=True``; reference shape: "Ragged
    Paged Attention", arxiv 2604.15464 / vLLM's PagedAttention): the KV
    cache is a ``[L, n_blocks, kvh, block_size, hd]`` block pool with a
    per-row block table and a host-side free-list
    (:class:`~paddle_tpu.inference.paged_cache.BlockAllocator`). Rows
    own ragged per-row lengths starting at their own position 0 —
    admission needs no global fill position, rows retire by freeing
    their pages, and the engine NEVER resets under sustained traffic
    (the contiguous cache's monotonic global fill shrank the admissible
    budget toward zero until an idle reset). The block table and lens
    are data arguments, so the two-compiled-programs discipline holds.

    ``paged=False`` keeps the contiguous right-aligned
    [L, capacity, s_max, kvh, hd] cache: finished rows retire, pending
    prompts admit into free slots, per-row left-pad offsets keep rope
    positions exact. On cache exhaustion it now runs a final CLAMPED
    chunk first: rows whose remaining max_new still fits in the leftover
    fill finish normally; only rows that genuinely cannot fit fail.

    Both modes: greedy outputs bit-match solo generation.
    ``device_steps`` counts executed decode steps — the efficiency
    metric batch-at-a-time loses (it always runs batch x max(max_new));
    ``resets`` counts cache resets (paged mode: stays at the
    construction-time 1)."""

    def __init__(self, model, capacity=4, s_max=256, chunk=8, pad_id=0,
                 paged=True, block_size=16, n_blocks=None,
                 prefix_cache=True, registry=None, worker_id=None,
                 prefix_listener=None, qos=None, chunked_prefill=False,
                 prefill_chunk=None, step_budget=None,
                 spec_decode=False, spec_max_draft=4, kv_dtype="fp",
                 mesh=None, tp_axis="tp", seq_axis="seq", profile=None,
                 recorder=None):
        from ..distributed.fleet.mp_layers import current_mesh
        from ..models.llama import _pp_degree
        if _pp_degree(current_mesh()) > 1:
            raise RuntimeError(
                "DecodeEngine needs the single-program decode path "
                "(pp=1); use BatchingServer's masked batch mode on "
                "pipeline meshes")
        self.model = model
        self.capacity = int(capacity)
        self.s_max = int(s_max)
        self.chunk = int(chunk)
        self.pad_id = int(pad_id)
        self.paged = bool(paged)
        self.block_size = int(block_size)
        self._prefix_on = bool(prefix_cache) and self.paged
        # ISSUE 10/16: mesh PARSE sits before the sizing defaults — the
        # 2-D mesh's seq degree shapes the n_blocks striping and the
        # default prefill chunk width. ``mesh=`` shards the paged block
        # pools (and int8 page scales) over the kv-head axis — and,
        # when the mesh carries a ``seq`` axis, their page axis too —
        # lowering every paged program through jit + shard_map; the
        # allocator, block tables, scheduler, prefix cache, and QoS
        # stay host-side and replicated, so r7-r14 semantics carry over
        # unchanged. mesh=None keeps the r14 single-device programs
        # bit-identical; a seq extent of 1 keeps the r15 1-D programs.
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.seq_axis = seq_axis
        self._tp = 1
        self._seq = 1
        if mesh is not None:
            if not self.paged:
                raise ValueError(
                    "mesh= requires the paged engine (the block pools "
                    "are what shards)")
            if tp_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} have no "
                    f"tp_axis={tp_axis!r}")
            self._tp = int(mesh.shape[tp_axis])
            if seq_axis in mesh.axis_names:
                self._seq = int(mesh.shape[seq_axis])
        # ISSUE 7: Sarathi-style chunked prefill. Admission allocates
        # pages but defers the prompt forward; decode_once() feeds
        # page-sized chunks through the r7 bucketed position-offset
        # prefill under a per-step token budget, so a long prompt
        # interleaves with decode instead of monopolizing the device at
        # admission. Greedy outputs stay bit-identical to the
        # admission-prefill path (the chunk program IS the prefix-tail
        # program whose bit-parity the r7 tests pin).
        self.chunked_prefill = bool(chunked_prefill)
        if self.chunked_prefill and not self.paged:
            raise ValueError(
                "chunked_prefill requires the paged engine (chunks "
                "scatter into the block pool)")
        # chunk size in tokens (default: one KV page PER SEQ SHARD —
        # context parallelism's scheduling dividend: a 2-D engine moves
        # seq× more prompt tokens per chunk launch at the same
        # per-shard page cost, so one giant prompt stops monopolizing
        # the step budget). Chunk windows ride the existing bucketed
        # prefix-prefill programs — powers of two from 16 — so chunking
        # compiles NO shape beyond the r7 bucket set. seq=1 keeps the
        # r19 one-page default byte-exactly.
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk \
            else self.block_size * self._seq
        if self.prefill_chunk <= 0:
            raise ValueError(f"prefill_chunk={prefill_chunk!r}")
        # per-step token budget: decode lanes claim theirs first, the
        # remainder funds prefill chunks (the scheduler owns the
        # funding order). Default: every decode lane plus one chunk.
        self.step_budget = int(step_budget) if step_budget \
            else self.capacity * self.chunk + self.prefill_chunk
        # r19 cross-worker KV transplant plumbing (migration.py): the
        # fused copy program lands in _transplant_prog lazily (compile-
        # tracker-wrapped when profiling), and tokens migrated INTO
        # this engine since the last step charge the next step's budget
        # as debt — KV bandwidth spent on this engine's behalf that the
        # pacing unit must still account for. Both stay at their zeros
        # unless a fleet actually migrates, keeping r18 bit-identical.
        self._mig_debt = 0
        self._transplant_prog = None
        # ISSUE 8: self-speculative decoding. The n-gram drafter
        # proposes up to spec_max_draft tokens per row; the engine
        # verifies all of them in ONE position-offset prefill step and
        # accepts the longest argmax-matching prefix. Default OFF —
        # prior outputs stay bit-identical.
        self.spec_decode = bool(spec_decode)
        self.spec_max_draft = int(spec_max_draft)
        if self.spec_decode and not self.paged:
            raise ValueError(
                "spec_decode requires the paged engine (the verify "
                "step rides the position-offset prefill programs)")
        if self.spec_decode and self.spec_max_draft < 1:
            raise ValueError(f"spec_max_draft={spec_max_draft}")
        self._drafter = None
        if self.spec_decode:
            from .spec_decode import NgramDrafter
            self._drafter = NgramDrafter(max_draft=self.spec_max_draft)
        # ISSUE 8: int8 paged KV. "int8" stores the block pools as int8
        # codes with one f32 scale per (layer, page, kv head) beside
        # them; writes quantize with a running-max scale, the attention
        # programs dequantize inside. Default "fp" keeps the r12 pools
        # and bit-identical outputs.
        if kv_dtype not in ("fp", "int8"):
            raise ValueError(f"kv_dtype={kv_dtype!r} (want 'fp' or "
                             f"'int8')")
        if kv_dtype == "int8" and not self.paged:
            raise ValueError("kv_dtype='int8' requires the paged "
                             "engine (scales live beside the block "
                             "pool)")
        self.kv_dtype = kv_dtype
        self._kv_q = kv_dtype == "int8"
        # stable identity inside a ServingFleet ("w0", "w1", ...) —
        # threaded into stats()/log lines so per-worker output is
        # distinguishable; None for a standalone engine.
        self.worker_id = worker_id
        self._prefix_listener = prefix_listener
        # ISSUE 6: multi-tenant QoS. A QoSPolicy swaps the pending queue
        # for a FairShareScheduler and arms a token-bucket gate on
        # submit(); qos=None keeps the r7 scheduler and bit-identical
        # behavior.
        if qos is not None and not self.paged:
            raise ValueError("qos requires the paged engine")
        self.qos = qos
        self._qos_gate = qos.gate() if qos is not None else None
        self._sched = None
        if self.paged:
            from .scheduler import RequestScheduler
            # table width covers within-chunk overflow writes of rows
            # that finish mid-chunk (their tail lands on the NULL page)
            self._max_blocks = -(-(self.s_max + self.chunk)
                                 // self.block_size)
            if n_blocks is None:
                # full occupancy never starves: every row can grow to
                # s_max (ceil(s_max/bs) pages), plus the reserved NULL
                # — per SEQ STRIPE, so each stripe can fund its share
                # of every row's column-striped pages (stripe 0 also
                # absorbs the NULL page). seq=1 reduces exactly to the
                # r7 formula.
                per = -(-self.s_max // self.block_size)
                n_blocks = self._seq * (
                    self.capacity * -(-per // self._seq) + 1)
            self.n_blocks = int(n_blocks)
            if qos is not None:
                from .qos import FairShareScheduler
                self._sched = FairShareScheduler(qos)
            else:
                self._sched = RequestScheduler()
        if mesh is not None:
            # aggregate divisibility check (satellite: EVERY violated
            # constraint in one message) — after n_blocks is known so
            # the page-striping requirement is included.
            from .sharding import validate_mesh_config
            validate_mesh_config(
                model.config, self._tp, self._seq,
                n_blocks=self.n_blocks if self.paged else None)
        self.device_steps = 0           # decode steps actually executed
        self.prefills = 0
        self.resets = 0                 # cache resets (init counts as 1)
        # ISSUE 3: lifecycle counters, latency histograms, and pool
        # gauges live in a metrics registry (private by default so two
        # engines in one process never pollute each other; pass
        # observability.get_registry() to aggregate process-wide).
        # stats() is a thin view over it.
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self._init_metrics()
        # ISSUE 13: step-phase profiler + recompile observatory.
        # profile=None (the default) creates NEITHER — phase guards
        # collapse to a no-op singleton and the compiled programs stay
        # unwrapped, so the hot path and outputs are untouched. Pass
        # profile=True (or a StepProfiler kwargs dict) to attach both;
        # recorder= threads a FlightRecorder so compile and step-outlier
        # events land beside the fleet's lifecycle events.
        self.flight = recorder
        self.profile = None
        self.compiles = None
        if profile:
            from ..observability.profiling import (CompileTracker,
                                                   StepProfiler)
            kw = dict(profile) if isinstance(profile, dict) else {}
            self.profile = StepProfiler(registry=self.metrics,
                                        recorder=recorder,
                                        worker_id=self.worker_id, **kw)
            self.compiles = CompileTracker(registry=self.metrics,
                                           recorder=recorder,
                                           worker_id=self.worker_id)
        # profile mode only: what each paged launch was handed (stats()),
        # and how many there were over the engine's life
        self._launches = collections.deque(maxlen=8192) if profile else None
        self._n_launches = 0
        self._scopes = {}       # program -> {instruction: named scope}
        self._build()
        self._reset()

    def _init_metrics(self):
        r = self.metrics
        self._c_admitted = r.counter(
            "engine_admitted_total", "requests admitted into a slot")
        self._c_retired = r.counter(
            "engine_retired_total", "requests finished cleanly")
        self._c_failed = r.counter(
            "engine_failed_total", "requests failed (admission or growth)")
        self._c_preempted = r.counter(
            "engine_preempted_total", "rows evicted for recompute-resume")
        self._c_prefix_hit = r.counter(
            "engine_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache")
        self._c_steps = r.counter(
            "engine_device_steps_total",
            "decode steps executed on device (stall-watchdog heartbeat)")
        self._c_prefills = r.counter(
            "engine_prefills_total", "admission prefill programs run")
        # how far the cold prefill's block walk engages: blocks of rows
        # it ran, beside what whole windows would have been
        self._c_prefill_blocks = r.counter(
            "engine_prefill_blocks_total",
            "blocks of rows run by cold prefills")
        self._c_prefill_window_blocks = r.counter(
            "engine_prefill_window_blocks_total",
            "blocks of rows in the windows of cold prefills")
        # ISSUE 10: device-call accounting — every compiled-program
        # launch (prefill/decode/verify/COW/mixed) bumps this, so the
        # single-launch mixed step's O(rows)->O(1) collapse is
        # observable next to engine_device_steps_total (which counts
        # decode WORK, not launches)
        self._c_device_calls = r.counter(
            "engine_device_calls_total",
            "compiled program launches (prefill, decode, verify, COW, "
            "mixed)")
        r.gauge("engine_tp_degree",
                "tensor-parallel degree of the engine's device mesh "
                "(1 = unsharded)",
                fn=lambda: self._tp)
        r.gauge("engine_seq_degree",
                "sequence-parallel degree of the engine's device mesh "
                "(pages sharded over the seq axis; 1 = unsharded)",
                fn=lambda: self._seq)
        # ISSUE 7: chunked-prefill observability beside the existing
        # prefill counter — chunks per step and the step's token load
        self._c_prefill_chunks = r.counter(
            "engine_prefill_chunks_total",
            "prefill chunks scheduled into decode steps")
        self._h_budget = r.histogram(
            "engine_step_budget_used",
            "tokens funded per engine step (decode lanes + prefill "
            "chunks)",
            buckets=tuple(float(2 ** i) for i in range(14)))
        self._h_ttft = r.histogram(
            "engine_ttft_seconds", "arrival to first emitted token")
        self._h_tpot = r.histogram(
            "engine_tpot_seconds", "per-output-token decode latency")
        self._h_queue_wait = r.histogram(
            "engine_queue_wait_seconds",
            "queued->admitted wait summed over preemption stints")
        self._h_chunk = r.histogram(
            "engine_chunk_seconds", "decode chunk device wall time")
        self._g_occupancy = r.gauge(
            "engine_batch_occupancy", "rows occupied by the last chunk")
        r.gauge("engine_backlog", "scheduler backlog depth",
                fn=lambda: self.backlog)
        if self.paged:
            # ISSUE 7 satellite: prefill DEBT, not just decode backlog
            # — the SLO engine and shed planner read this beside
            # engine_backlog to see queued prompt tokens still owed
            r.gauge("engine_prefill_backlog_tokens",
                    "queued + admitted prompt tokens not yet prefilled",
                    fn=lambda: self.prefill_backlog)
            # pool gauges read the allocator at COLLECTION time — one
            # source of truth, no mirrored counters to drift
            r.gauge("engine_pool_free", "free pages in the block pool",
                    fn=lambda: self._alloc.num_free)
            r.gauge("allocator_in_use", "pages with live references",
                    fn=lambda: self._alloc.in_use)
            r.gauge("engine_pool_high_watermark",
                    "max pages ever in use at once",
                    fn=lambda: self._alloc.high_watermark)
            if self._prefix_on:
                r.gauge("engine_prefix_hit_rate",
                        "fraction of admissions matching any cached "
                        "prefix",
                        fn=lambda: (self._cache.hit_rate
                                    if self._cache is not None else 0.0))
        self._c_decode_row_steps = r.counter(
            "engine_decode_row_steps_total",
            "rows summed over the steps of paged decode chunks (over "
            "engine_device_steps_total: rows a step)")
        # per-slot recurrent state (a family whose PagedPrograms has
        # ``slot_state``; all three stay 0 for every other model)
        self._c_ssm_row_steps = r.counter(
            "engine_ssm_row_steps_total",
            "live rows summed over the decode steps of a model with "
            "per-slot state (over engine_device_steps_total: rows a "
            "step)")
        self._c_ssm_prefill_chunks = r.counter(
            "engine_ssm_prefill_chunks_total",
            "chunks of the recurrence run by cold prefills")
        # the order of the loop's work: a paged program is handed to the
        # chip before the last one's result is read back
        self._c_launch_ahead = r.counter(
            "engine_launch_ahead_total",
            "paged launches issued with a result still unread: a "
            "prefill left unread, a decode chunk behind one")
        self._c_settle_early = r.counter(
            "engine_settle_early_total",
            "times a path had to read the unread first tokens before "
            "it could launch")
        self._c_decode_ctx = r.counter(
            "engine_decode_ctx_tokens_total",
            "context lengths of live rows summed over the steps of "
            "paged decode chunks (what the paged reads walk)")
        r.gauge("engine_state_slots_in_use",
                "slots that hold a live row's recurrent state",
                fn=lambda: len(self._state_slots))
        if self.paged and self.spec_decode:
            # ISSUE 8: speculation observability. accepted counts BONUS
            # tokens only (m-1 per verify step: the first token is what
            # a plain decode step would have produced anyway), so
            # accepted/proposed is the draft survival rate and
            # accept_len's mean is tokens/step.
            self._c_spec_proposed = r.counter(
                "engine_spec_proposed_total",
                "draft tokens submitted to verify steps")
            self._c_spec_accepted = r.counter(
                "engine_spec_accepted_total",
                "draft tokens accepted (emitted beyond the per-step "
                "baseline)")
            self._h_spec_accept = r.histogram(
                "engine_spec_accept_len",
                "tokens emitted per verify step (1 = every draft "
                "rejected)",
                buckets=tuple(float(i) for i in
                              range(1, self.spec_max_draft + 2)))
            r.gauge(
                "engine_spec_accept_rate",
                "accepted/proposed draft token fraction",
                fn=lambda: (self._c_spec_accepted.value
                            / self._c_spec_proposed.value
                            if self._c_spec_proposed.value else 0.0))

    # -- compiled programs --------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        from ..models import llama as _llama
        from ..models import paged_stack as _seam
        m = self.model
        cfg = m.config
        self._names = m._stacked_names()
        self._scales = getattr(m, "_quant_scales", None) or {}
        # ISSUE 10: inside a shard_map region the paged programs run on
        # kv-head shards and finish row-parallel matmuls with a psum
        # over this axis; mesh=None compiles the identical r14 programs
        # (mp=None makes every _mp_sum the identity). ISSUE 16: ``sq``
        # additionally page-shards the pools — pool writes rebase
        # through ownership masks and attention merges per-shard
        # softmax partials. A seq extent of 1 threads sq=None, so the
        # r15 1-D programs compile byte-identically.
        mp = self.tp_axis if self.mesh is not None else None
        sq = self.seq_axis \
            if self.mesh is not None and self._seq > 1 else None
        n_sq = self._seq

        def _weights():
            st = {n: m._parameters[n]._value for n in self._names}
            lm = m._parameters["lm_head"]._value \
                if m._parameters.get("lm_head") is not None else None
            embed = m._parameters["embed_tokens"]._value
            return st, embed, m._parameters["final_norm"]._value, lm

        self._weights = _weights

        def prefill(stacked, embed, fnorm, lm, scales, ids, pad_len, g):
            """ids [1, sc] (prompt right-aligned to end at slot g);
            returns (first_tok [1], ks, vs [L, 1, sc, kvh, hd]). int8
            weights dequantize INSIDE the program (scales={} = no-op)."""
            stacked, lm = _llama._dequantize_weights(cfg, stacked, lm,
                                                     scales)
            if lm is None:
                lm = embed.T
            logits, ks, vs = _llama.masked_prefill(
                cfg, stacked, embed, fnorm, lm, ids, pad_len,
                last_index=g - 1)
            return jnp.argmax(logits, axis=-1), ks, vs

        def make_decode(n):
            """Contiguous decode program over ``n`` steps. ``n`` is the
            engine chunk for the whole lifetime except ONE final
            clamped chunk at cache exhaustion (satellite: near-finished
            rows ride the leftover fill out instead of failing)."""

            def decode_chunk(stacked, embed, fnorm, lm, scales, tok, ck,
                             cv, g0, pad_len):
                stacked, lm = _llama._dequantize_weights(cfg, stacked,
                                                         lm, scales)
                if lm is None:
                    lm = embed.T

                def body(carry, i):
                    tok, ck, cv = carry
                    logits, ck, cv = _llama._decode_step(
                        cfg, stacked, embed, fnorm, lm, tok, ck, cv,
                        g0 + i, pad_len=pad_len)
                    nxt = jnp.argmax(logits, axis=-1)
                    return (nxt, ck, cv), nxt

                (tok, ck, cv), toks = jax.lax.scan(
                    body, (tok, ck, cv), jnp.arange(n))
                return toks, ck, cv

            return decode_chunk

        # The two programs every paged engine runs, the cold prefill and
        # the decode chunk, are the model family's (``paged_programs``,
        # models.paged_stack.PagedPrograms), with the geometry of its block
        # pool and whatever a slot holds beside its pages. They take the
        # pool arrays LAST as ``*pool`` (ISSUE 8): fp engines pass
        # (kp, vp), int8 engines (kp, vp, kscale, vscale), and a family
        # with per-slot state its state arrays behind them.
        _kv_scales_of = _seam.kv_scales_of
        self._prefill_block = _seam.prefill_block_rows(cfg, self.s_max)
        progs = m.paged_programs(
            chunk=self.chunk, prefill_block=self._prefill_block,
            mp_axis=mp, seq_axis=sq, n_seq=n_sq)
        self._refuse_unsupported(progs.unsupported)
        self._progs = progs
        prefill_paged = progs.prefill_paged
        decode_chunk_paged = progs.decode_chunk_paged

        def make_prefix_prefill(sc):
            """Prefix-hit prefill over a BUCKETED tail window of ``sc``
            slots: the cached prefix stays in the pool, only the
            uncached tail runs the forward — the TTFT win prefix
            sharing exists for. One program per bucket (powers of two);
            cold admissions run the one blockwise program."""

            def prefill_prefix(stacked, embed, fnorm, lm, scales, ids,
                               pad_len, prefix_len, table_row, *pool):
                stacked, lm = _llama._dequantize_weights(cfg, stacked,
                                                         lm, scales)
                if lm is None:
                    lm = embed.T
                out = _llama.prefix_prefill(
                    cfg, stacked, embed, fnorm, lm, ids, pad_len,
                    prefix_len, pool[0], pool[1], table_row,
                    kv_scales=_kv_scales_of(pool), mp_axis=mp,
                    seq_axis=sq, n_seq=n_sq)
                return (jnp.argmax(out[0], axis=-1), *out[1:])

            return prefill_prefix

        def make_verify_prefill(sc):
            """Speculative VERIFY program over a bucketed ``sc`` window
            (ISSUE 8): the tail is the row's pending next-input token
            plus its k drafts at ``prefix_len = tokens-resident``, and
            the program returns the argmax at EVERY window position —
            the engine reads the greedy chain off the last k+1 slots
            and accepts the longest prefix the drafts matched. Same
            math as the prefix-prefill program (r7/r12 parity), one new
            compiled shape per bucket."""

            def verify_prefill(stacked, embed, fnorm, lm, scales, ids,
                               pad_len, prefix_len, table_row, *pool):
                stacked, lm = _llama._dequantize_weights(cfg, stacked,
                                                         lm, scales)
                if lm is None:
                    lm = embed.T
                out = _llama.prefix_prefill(
                    cfg, stacked, embed, fnorm, lm, ids, pad_len,
                    prefix_len, pool[0], pool[1], table_row,
                    kv_scales=_kv_scales_of(pool), all_logits=True,
                    mp_axis=mp, seq_axis=sq, n_seq=n_sq)
                return (jnp.argmax(out[0], axis=-1), *out[1:])

            return verify_prefill

        def mixed_step(stacked, embed, fnorm, lm, scales, ids, q_lens,
                       kv_lens, tables, *pool):
            """ISSUE 10 single-launch step: decode rows, verify windows
            and prefill chunks ride ONE ``mixed_paged_attention``
            program — ids [B, T] LEFT-aligned with per-row q_lens,
            kv_lens INCLUDING this launch's tokens. Returns the argmax
            at every window position (the engine reads greedy chains /
            chunk boundaries off it host-side)."""
            stacked, lm = _llama._dequantize_weights(cfg, stacked, lm,
                                                     scales)
            if lm is None:
                lm = embed.T
            return _llama.mixed_paged_step(
                cfg, stacked, embed, fnorm, lm, ids, q_lens, kv_lens,
                tables, *pool, mp_axis=mp, seq_axis=sq, n_seq=n_sq)

        def cow_copy(src, dst, *pool):
            """Copy-on-write: clone page ``src`` into the row's private
            page ``dst`` (both pools, all layers; int8 engines copy the
            page scales with the codes). src/dst are DATA, so every COW
            admission reuses this one program."""
            out = tuple(a.at[:, dst].set(a[:, src]) for a in pool)
            return out

        def cow_copy_seq(src, dst, *pool):
            """Page-sharded COW (2-D mesh): the striped allocator
            guarantees src and dst occupy the SAME table column, hence
            the same stripe — so the copy is shard-LOCAL (no cross-seq
            collective). Non-owning shards clamp the read and drop the
            write."""
            n_local = pool[0].shape[1]
            off0 = jax.lax.axis_index(sq) * n_local
            rs = jnp.clip(src - off0, 0, n_local - 1)
            owned = (dst >= off0) & (dst < off0 + n_local)
            wd = jnp.where(owned, dst - off0, n_local)
            return tuple(a.at[:, wd].set(a[:, rs], mode="drop")
                         for a in pool)

        self._make_decode = make_decode
        self._decode_progs = {}
        self._make_prefix_prefill = make_prefix_prefill
        self._prefix_progs = {}
        self._make_verify_prefill = make_verify_prefill
        self._verify_progs = {}
        self._state_specs = tuple(progs.slot_state(self.capacity)) \
            if progs.slot_state is not None else ()
        self._n_pool = (4 if self._kv_q else 1 + progs.value_pool) \
            + len(self._state_specs)
        # the counters' vector (the last state array) goes in and comes
        # out like the rest but is NOT donated: the one a launch returned
        # stays readable from any thread while the next launch runs
        n_donated = self._n_pool - bool(progs.device_counters)
        if self.paged and self.mesh is not None:
            # ISSUE 10: lower every paged program through shard_map
            # over the kv-head axis. Weights shard Megatron column/row,
            # pools shard on kv heads, host data (ids, tables, lens)
            # replicates, and outputs replicate (the programs finish
            # row-parallel matmuls with a psum, so every shard holds
            # identical logits/tokens).
            from jax.sharding import NamedSharding as _NS
            from jax.sharding import PartitionSpec as _P

            from .sharding import (pool_specs, quant_scale_specs,
                                   stacked_weight_specs)
            _R = _P()
            ax = self.tp_axis
            wsp = stacked_weight_specs(self._names, ax)
            ssp = quant_scale_specs(self._scales, ax)
            psp = pool_specs(self._n_pool, ax, seq_axis=sq)

            def _tp_wrap(fn, n_data):
                """(weights..., scales, <n_data host args>, *pool) →
                sharded program with replicated outputs. A ``P()``
                prefix covers the tied-embedding case (lm=None has no
                leaves to place)."""
                return jax.shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(wsp, _R, _R, _R, ssp,
                              *([_R] * n_data), *psp),
                    out_specs=(_R, *psp))

            cow_wrapped = jax.shard_map(
                cow_copy_seq if sq is not None else cow_copy,
                mesh=self.mesh, in_specs=(_R, _R, *psp),
                out_specs=psp)

            def _placed_weights(_cache={}):
                # device_put ONCE per engine: stacked weights land
                # pre-sharded so each launch ships no weight bytes.
                if "w" not in _cache:
                    st, embed, fnorm, lm = _weights()
                    put = lambda a, sp: jax.device_put(
                        a, _NS(self.mesh, sp))
                    st = {n: put(v, wsp[n]) for n, v in st.items()}
                    _cache["w"] = (st, put(embed, _R), put(fnorm, _R),
                                   None if lm is None else put(lm, _R))
                return _cache["w"]

            self._weights = _placed_weights
            self._scales = {n: jax.device_put(
                v, _NS(self.mesh, ssp[n]))
                for n, v in self._scales.items()}
        else:
            def _tp_wrap(fn, n_data):
                return fn

            cow_wrapped = cow_copy
        self._tp_wrap = _tp_wrap
        if self.paged:
            # every paged program takes the pools donated and leaves
            # them where they lie; a family with slot state hands its
            # prefill the row's slot behind the table
            if progs.slot_state is None:
                self._prefill = jax.jit(
                    _tp_wrap(prefill_paged, 3),
                    donate_argnums=tuple(range(8, 8 + self._n_pool)))
            else:
                self._prefill = jax.jit(
                    prefill_paged,
                    donate_argnums=tuple(range(9, 9 + n_donated)))
            self._decode = jax.jit(
                _tp_wrap(decode_chunk_paged, 3),
                donate_argnums=tuple(range(8, 8 + n_donated)))
            self._cow = jax.jit(
                cow_wrapped,
                donate_argnums=tuple(range(2, 2 + self._n_pool)))
            self._mixed = jax.jit(
                _tp_wrap(mixed_step, 4),
                donate_argnums=tuple(range(9, 9 + self._n_pool)))
            # the next input token of every slot is a device vector the
            # launches hand on: a prefill leaves its first token at
            # [slot] (or ``known``, what the host has of a resumed row;
            # ``at`` is [slot, known or -1], one upload), a decode chunk
            # its last row. Dispatched behind the launch, while the chip
            # is busy; never donated
            self._tok_put = jax.jit(
                lambda tok, at, first: tok.at[at[0]].set(
                    jnp.where(at[1] >= 0, at[1], first[0])))
            self._tok_last = jax.jit(lambda toks: toks[-1])
            if self.compiles is not None:
                # ISSUE 13 recompile observatory: each wrapped program
                # logs (name, abstract shapes, wall) on every NEW
                # argument signature — a post-warmup entry is a
                # recompile the bucket discipline should have prevented
                # (runtime twin of the SC06 static checker).
                self._prefill = self.compiles.wrap(
                    "prefill_paged", self._prefill)
                self._decode = self.compiles.wrap(
                    "decode_chunk_paged", self._decode)
                self._cow = self.compiles.wrap("cow_copy", self._cow)
                self._mixed = self.compiles.wrap(
                    "mixed_step", self._mixed)
        else:
            self._prefill = jax.jit(prefill)
            if self.compiles is not None:
                self._prefill = self.compiles.wrap(
                    "prefill", self._prefill)
            self._decode = self._decode_for(self.chunk)
        self._cfg = cfg
        self._kvh = progs.kv_heads
        self._hd = progs.head_dim
        self._hdv = progs.v_head_dim or progs.head_dim
        self._L = progs.kv_layers
        # what the family's programs count on the device: the last
        # state array, read by stats() alone
        self._c_device = tuple(
            self.metrics.counter(
                f"engine_{name}_total",
                f"{name}, counted on the device by the paged programs")
            for name in progs.device_counters)
        # what the family has the engine count at every decode launch
        self._c_host = tuple(
            (fn, self.metrics.counter(
                f"engine_{name}_total",
                f"{name}, counted by the engine at every decode launch "
                f"from the live rows' contexts"))
            for name, fn in progs.host_counters.items())
        self._counts_lock = threading.Lock()
        self._cache_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" \
            else jnp.float32

    def _refuse_unsupported(self, unsupported):
        """Raise for every option of this engine that the model family
        says it cannot serve (``PagedPrograms.unsupported``), by name."""
        asked = {"prefix_cache": self._prefix_on,
                 "paged=False": not self.paged,
                 "chunked_prefill": self.chunked_prefill,
                 "spec_decode": self.spec_decode,
                 "kv_dtype='int8'": self._kv_q,
                 "mesh": self.mesh is not None}
        hit = [f"{opt}: {why}" for opt, why in unsupported.items()
               if asked.get(opt)]
        if hit:
            raise ValueError(
                f"{type(self.model).__name__} cannot be served with "
                + "; ".join(hit))

    def _decode_for(self, n):
        """Compiled contiguous decode program for an ``n``-step chunk
        (cached; in practice only self.chunk plus at most one clamped
        tail length per workload)."""
        import jax
        fn = self._decode_progs.get(n)
        if fn is None:
            fn = jax.jit(self._make_decode(n), donate_argnums=(6, 7))
            if self.compiles is not None:
                fn = self.compiles.wrap("decode_chunk", fn, key=n)
            self._decode_progs[n] = fn
        return fn

    def _bucket_window(self, n: int) -> int:
        """Tail-window bucket for prefix-hit prefill: powers of two from
        16, capped at s_max — mixed tail lengths share a few compiled
        programs, and the bucket being SMALLER than the full s_max
        window is where the cached-TTFT win comes from."""
        b = 16
        while b < n:
            b *= 2
        return min(b, self.s_max)

    def _prefix_prefill_for(self, sc):
        import jax
        fn = self._prefix_progs.get(sc)
        if fn is None:
            fn = jax.jit(self._tp_wrap(self._make_prefix_prefill(sc),
                                       4),
                         donate_argnums=tuple(
                             range(9, 9 + self._n_pool)))
            if self.compiles is not None:
                fn = self.compiles.wrap("prefix_prefill", fn, key=sc)
            self._prefix_progs[sc] = fn
        return fn

    def _verify_prefill_for(self, sc):
        """Compiled verify program for an ``sc``-slot window (cached;
        with the default draft cap every window is the 16-slot
        bucket)."""
        import jax
        fn = self._verify_progs.get(sc)
        if fn is None:
            fn = jax.jit(self._tp_wrap(self._make_verify_prefill(sc),
                                       4),
                         donate_argnums=tuple(
                             range(9, 9 + self._n_pool)))
            if self.compiles is not None:
                fn = self.compiles.wrap("verify_prefill", fn, key=sc)
            self._verify_progs[sc] = fn
        return fn

    def _reset(self):
        import jax
        import jax.numpy as jnp
        import numpy as _np
        from jax.sharding import NamedSharding, PartitionSpec
        self.resets += 1
        B = self.capacity
        # what a slot holds beside its pages (a recurrent model's
        # states): zero until a prefill leaves a row's there
        self._sync_device_counters()
        if self._launches:
            self._launches.clear()      # its counts restart with the state
        with self._counts_lock:
            self._state = tuple(jnp.zeros(sp.shape, sp.dtype)
                                for sp in self._state_specs)
            self._device_counts_seen = _np.zeros(len(self._c_device),
                                                 _np.int64)
        self._state_slots = set()
        if self.paged:
            from .paged_cache import BlockAllocator
            from .prefix_cache import PrefixCache
            pool_dtype = jnp.int8 if self._kv_q else self._cache_dtype
            self._kp = jnp.zeros((self._L, self.n_blocks, self._kvh,
                                  self.block_size, self._hd),
                                 pool_dtype)
            # a family with one kind of page has no second pool
            self._vp = jnp.zeros(self._kp.shape[:-1] + (self._hdv,),
                                 pool_dtype) \
                if self._progs.value_pool else None
            if self._kv_q:
                from ..kernels.paged_attention import KV_SCALE_EPS
                self._kscale = jnp.full(
                    (self._L, self.n_blocks, self._kvh),
                    KV_SCALE_EPS, jnp.float32)
                self._vscale = jnp.full_like(self._kscale,
                                             KV_SCALE_EPS)
            if self.mesh is not None:
                # ISSUE 10: the pools live pre-sharded over the kv-head
                # axis — donated through every program, they stay
                # sharded for the engine's lifetime.
                from .sharding import pool_specs
                psp = pool_specs(
                    4 if self._kv_q else 2, self.tp_axis,
                    seq_axis=(self.seq_axis if self._seq > 1 else None))
                put = lambda a, sp: jax.device_put(
                    a, NamedSharding(self.mesh, sp))
                self._kp = put(self._kp, psp[0])
                self._vp = put(self._vp, psp[1])
                if self._kv_q:
                    self._kscale = put(self._kscale, psp[2])
                    self._vscale = put(self._vscale, psp[3])
            self._alloc = BlockAllocator(self.n_blocks,
                                         stripes=self._seq)
            # int8: recycled pages must drop the previous tenant's
            # running-max scale before their next write
            self._alloc.track_allocations = self._kv_q
            self._cache = PrefixCache(self._alloc, self.block_size,
                                      listener=self._prefix_listener) \
                if self._prefix_on else None
            self._tables = _np.zeros((B, self._max_blocks), _np.int32)
            self._lens = _np.zeros((B,), _np.int32)
        else:
            self._ck = jnp.zeros((self._L, B, self.s_max, self._kvh,
                                  self._hd), self._cache_dtype)
            self._cv = jnp.zeros_like(self._ck)
            self._g = 0
            self._pad = _np.zeros((B,), _np.int32)
        # every slot's next input token: on the device in paged mode
        # (``_tok_put`` / ``_tok_last``), on the host in contiguous mode
        self._tok = _np.zeros((B,), _np.int32)
        if self.paged:
            self._tok = jnp.asarray(self._tok)
            if self.mesh is not None:
                self._tok = jax.device_put(
                    self._tok, NamedSharding(self.mesh, PartitionSpec()))
        # prefills launched whose first token is not on the host yet:
        # (first, slot, row) in launch order, read by ``_settle``
        self._unread = collections.deque()
        self._rows = [None] * B         # per-slot host state

    # -- pool plumbing (ISSUE 8) --------------------------------------------
    def _pool(self):
        """The device arrays every paged program takes LAST: (kp, vp)
        for fp pools ((kp,) for a family with one kind of page), (kp, vp,
        kscale, vscale) for int8; behind them a stateful family's
        per-slot state arrays."""
        if self._kv_q:
            return (self._kp, self._vp, self._kscale, self._vscale)
        pages = (self._kp,) if self._vp is None else (self._kp, self._vp)
        return (*pages, *self._state)

    def _set_pool(self, vals):
        if self._kv_q:
            self._kp, self._vp, self._kscale, self._vscale = vals
        else:
            self._kp, *state = vals
            if self._vp is not None:
                self._vp, *state = state
            self._state = tuple(state)

    def _sync_device_counters(self):
        """Bring the counters the paged programs keep on the device
        (``PagedPrograms.device_counters``) into the registry: one small
        fetch, made by ``stats()`` and before a reset, never by a step.
        The vector is never donated, so the one the last launch returned
        can be read from any thread (the read waits for that launch).
        The device's int32 may wrap; the difference since the last fetch
        does not."""
        if not self._c_device or not getattr(self, "_state", ()):
            return                      # none kept, or the first reset
        import numpy as _np
        with self._counts_lock:
            now = _np.asarray(self._state[-1]).astype(_np.int64)
            for c, new, old in zip(self._c_device, now,
                                   self._device_counts_seen):
                c.inc(int((new - old) % (1 << 32)))
            self._device_counts_seen = now

    def _launch_args(self, kind, units, rows, tokens):
        """Profile mode: what the next launch of one of the two paged
        programs is handed, for its ``launch`` span's trace annotation
        and, once it has returned, for ``_note_launch``: ``launch``
        counts on from launch to launch over the engine's life, decode
        and prefill alike, so it orders them; ``"prefill"`` (``units``
        blocks of ``tokens`` prompt tokens) or ``"decode"`` (``units``
        steps of ``rows`` live rows that read ``tokens`` cached tokens
        in all). None with profiling off."""
        if self._launches is None:
            return None
        self._n_launches += 1
        return {"launch": self._n_launches - 1, "kind": kind,
                "units": units, "rows": rows, "tokens": tokens}

    def _note_launch(self, t, launch):
        """Profile mode: one entry a launch of the two paged programs,
        so that a reader of a device trace can count the work of the
        launches it traced and not of the run's mean: when it was
        dispatched (``observability.now``), what ``_launch_args`` said
        of it, and the device counters' vector as that launch returned
        it, kept by reference: nothing is fetched here."""
        if launch is not None:
            self._launches.append(
                (t, launch["kind"], launch["units"], launch["rows"],
                 launch["tokens"],
                 self._state[-1] if self._c_device else None,
                 tuple(int(c.value) for _, c in self._c_host)))

    def _scope_avals(self, name, args):
        """Profile mode, for a family that names ``trace_scopes``: the
        shapes of a launch's arguments while program ``name``'s scopes
        are still to be read (taken before the launch donates them);
        None otherwise."""
        if self._launches is None or name in self._scopes \
                or not self._progs.trace_scopes:
            return None
        import jax
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)

    def _note_scopes(self, name, fn, avals):
        """Once a paged program: which named scope each instruction of
        the compiled program lies under, read from the text of the
        executable its first launch built: lowering the same shapes
        again finds that executable in the jit's own cache and compiles
        nothing (``tests/test_glm_moe_dsa.py`` counts the backend's
        compiles round this call). Profile mode only, and a failure
        raises: a table that silently came out empty would leave every
        reader of it with nothing to read."""
        if avals is None:
            return
        from ..observability.profiling import scope_map
        fn = getattr(fn, "__wrapped__", fn)     # under CompileTracker.wrap
        self._scopes[name] = scope_map(fn.lower(*avals).compile().as_text(),
                                       self._progs.trace_scopes)

    def _launch_entries(self):
        """``[t, kind, units, rows, tokens, *device counters, *host
        counters, *the device counters of _LOG_LAST]`` a launch, oldest
        first; the counters run on from launch to launch (the device's
        are int32 and may wrap)."""
        import jax
        log = list(self._launches.copy())
        counts = jax.device_get([e[5] for e in log]) if self._c_device \
            else [()] * len(log)
        names = self._progs.device_counters
        ahead = [i for i, n in enumerate(names) if n not in _LOG_LAST]
        last = [i for i, n in enumerate(names) if n in _LOG_LAST]
        return [[*e[:5], *(int(c[i]) for i in ahead), *e[6],
                 *(int(c[i]) for i in last)]
                for e, c in zip(log, counts)]

    def _drain_scale_resets(self):
        """int8 only: reset the scales of pages the allocator handed
        out since the last drain back to the eps floor. A recycled page
        keeps its codes (garbage until overwritten, masked by lens) but
        must NOT keep the previous tenant's running-max scale — scales
        only grow, so a stale one would permanently coarsen every new
        row quantized into the page. Runs BEFORE any program that
        writes KV (and before COW, so a copied scale isn't clobbered)."""
        if not self._kv_q:
            return
        dirty = self._alloc.drain_allocated()
        if not dirty:
            return
        import jax.numpy as jnp
        from ..kernels.paged_attention import KV_SCALE_EPS
        idx = jnp.asarray(dirty, jnp.int32)
        self._kscale = self._kscale.at[:, idx].set(KV_SCALE_EPS)
        self._vscale = self._vscale.at[:, idx].set(KV_SCALE_EPS)

    # -- engine loop pieces -------------------------------------------------
    def _no_rows(self) -> bool:
        return all(r is None for r in self._rows)

    def idle(self) -> bool:
        """Nothing to do: no live rows AND no scheduler backlog (a
        request waiting on pages is work in flight, not idleness — the
        serving loop and drive harnesses key off this)."""
        return self._no_rows() and not self.backlog

    @property
    def backlog(self) -> int:
        """Requests the scheduler holds that no slot/pages could fund
        yet."""
        return len(self._sched) if self._sched is not None else 0

    @property
    def prefill_backlog(self) -> int:
        """Prompt tokens not yet prefilled (ISSUE 7 satellite): queued
        requests' whole prompts plus admitted chunked rows' unprefilled
        remainders — the prefill DEBT the decode-depth ``backlog``
        gauge cannot see."""
        if self._sched is None:
            return 0
        tokens = self._sched.pending_tokens()
        for row in self._rows:
            if row is not None and "pf_seq" in row:
                tokens += row["pf_seq"].size - row["pf_pos"]
        return tokens

    def drain_pending(self) -> list:
        """Remove and return every scheduled-but-unadmitted request
        (server shutdown path)."""
        return self._sched.drain() if self._sched is not None else []

    def stats(self) -> dict:
        """Engine observability: a thin view over the metrics registry
        (lifecycle counters) plus pool occupancy (including the
        allocator's high-watermark) and prefix-cache hit accounting.
        ``metrics.snapshot()`` is the full registry (histograms with
        TTFT/TPOT/queue-wait buckets included); this keeps the r6/r7
        dict shape."""
        s = {"worker_id": self.worker_id,
             "admitted": int(self._c_admitted.value),
             "retired": int(self._c_retired.value),
             "failed": int(self._c_failed.value),
             "preempted": int(self._c_preempted.value),
             "prefix_hit_tokens": int(self._c_prefix_hit.value),
             "device_steps": self.device_steps,
             "device_calls": int(self._c_device_calls.value),
             "tp_degree": self._tp,
             "seq_degree": self._seq,
             "prefills": self.prefills,
             "prefill_blocks": int(self._c_prefill_blocks.value),
             "prefill_window_blocks":
                 int(self._c_prefill_window_blocks.value),
             "decode_row_steps": int(self._c_decode_row_steps.value),
             "decode_ctx_tokens": int(self._c_decode_ctx.value),
             "launch_ahead": int(self._c_launch_ahead.value),
             "settle_early": int(self._c_settle_early.value),
             "resets": self.resets}
        self._sync_device_counters()
        for name, c in zip(self._progs.device_counters, self._c_device):
            s[name] = int(c.value)
        for name, (_, c) in zip(self._progs.host_counters, self._c_host):
            s[name] = int(c.value)
        if self._launches is not None:
            s["launches"] = self._launch_entries()
            if self._scopes:
                s["scopes"] = dict(self._scopes)
        if self._progs.chunks_per_block:    # a recurrent family's own
            s["ssm_row_steps"] = int(self._c_ssm_row_steps.value)
            s["ssm_prefill_chunks"] = int(self._c_ssm_prefill_chunks.value)
        if self._state_specs:
            s["state_slots_in_use"] = len(self._state_slots)
        if self.mesh is not None:
            s["mesh_shape"] = {k: int(v)
                               for k, v in self.mesh.shape.items()}
        if self.paged:
            s["pool"] = self._alloc.stats()
            s["backlog"] = self.backlog
            s["prefill_backlog"] = self.prefill_backlog
            s["prefill_chunks"] = int(self._c_prefill_chunks.value)
            if self._cache is not None:
                s["prefix_cache"] = self._cache.stats()
            if self.spec_decode:
                prop = int(self._c_spec_proposed.value)
                acc = int(self._c_spec_accepted.value)
                steps = int(self._h_spec_accept.count)
                s["spec"] = {
                    "proposed": prop,
                    "accepted": acc,
                    "accept_rate": acc / prop if prop else 0.0,
                    "verify_steps": steps,
                    # emitted per verify step (accepted run INCLUDES the
                    # free base token, so this floors at 1.0)
                    "tokens_per_step":
                        self._h_spec_accept.sum / steps if steps else 0.0,
                }
        return s

    # -- launch ahead of the read-back --------------------------------------
    @staticmethod
    def _fetch(arr):
        """A device array's value on the host (blocks until it is
        there; a launch that failed raises here)."""
        return np.asarray(arr)

    def _settle(self, early=False):
        """Read, in launch order, the first token of every prefill that
        was launched and left unread: the row gets its token, and its
        ``first_token`` mark is made now, when the value is on the host.
        The plain paged step calls this behind its decode launch;
        whatever needs a token's value BEFORE it can launch (drafting,
        the mixed step, a chunked prompt's last chunk, a preemption that
        parks ``row["toks"]``) calls it ``early``. A read that raises
        fails its own request, as a launch that raises does."""
        if not self._unread:
            return
        if early:
            self._c_settle_early.inc()
        while self._unread:
            first, slot, row = self._unread.popleft()
            if self._rows[slot] is not row:
                continue                # failed or cleared meanwhile
            try:
                with _phase(self.profile, "host_sync"):
                    tok = int(self._fetch(first)[0])
            except Exception as e:  # noqa: BLE001 — fail THIS request,
                self._fail_row_paged(slot, e)  # not the whole engine
                continue
            row["toks"].append(tok)
            self._observe_first_token(row["req"])

    def _hand_on(self, slot, first, known=None):
        """Behind a prefill's launch: ``slot``'s next input token goes
        into the device's vector, the prefill's ``first`` (``[1]``, on
        the device; its copy to the host is asked for now, so that it
        leaves when the prefill ends whatever is queued behind) or, if
        the host has it already (a resumed row's), ``known``."""
        if known is None:
            first.copy_to_host_async()
        self._tok = self._tok_put(
            self._tok,
            np.array([slot, -1 if known is None else known], np.int32),
            first)

    def _hand_on_known(self, slot, tok):
        """The mixed step read ``slot``'s next input itself: it goes
        into the device's vector for the chunk program, which serves
        the steps with no prompt under way (and never a speculative
        engine)."""
        if not self.spec_decode:
            self._hand_on(slot, np.zeros((1,), np.int32), tok)

    # -- lifecycle telemetry (ISSUE 3) --------------------------------------
    def _trace_admission(self, req):
        """Close this stint's queued->admitted wait (a preempted
        request opens a fresh stint per re-queue). The admitted COUNTER
        only increments after the prefill succeeds — this runs when
        admission starts, so queue wait excludes prefill time."""
        tr = getattr(req, "trace", None)
        if tr is None:
            return
        t_adm = tr.mark("admitted", worker=self.worker_id)
        tq = tr.last("queued")
        self._h_queue_wait.observe(
            t_adm - (tq if tq is not None else tr.arrival))

    def _observe_first_token(self, req):
        """TTFT from the trace — only on the FIRST token ever (a
        resumed request already emitted one before preemption)."""
        tr = getattr(req, "trace", None)
        if tr is None:
            return
        tf = tr.mark_once("first_token", worker=self.worker_id)
        if tf is not None:
            self._h_ttft.observe(tf - tr.arrival)

    def _observe_retired(self, req):
        self._c_retired.inc()
        tr = getattr(req, "trace", None)
        if tr is None:
            return
        t_ret = tr.mark("retired", worker=self.worker_id)
        tf = tr.first("first_token")
        if tf is not None and req.max_new > 1:
            self._h_tpot.observe((t_ret - tf) / (req.max_new - 1))
        log_kv(_log, "retired", level=logging.DEBUG,
               worker=self.worker_id,
               req=tr.request_id, new_tokens=req.max_new,
               ttft_s=round(tr.ttft, 6) if tr.ttft is not None else None,
               preemptions=tr.preemptions)

    def admit(self, pending):
        """Move requests from ``pending`` (a list; consumed in order)
        into free slots. Paged mode: every request enters the
        RequestScheduler (priority + FCFS) and admission runs highest
        priority first, charging only the UNCACHED suffix pages after a
        prefix-cache match; when the pool runs short, unreferenced
        cached pages are evicted and strictly-lower-priority running
        rows are preempted for recompute-resume before admission waits.
        Contiguous mode: a prompt longer than the current global fill
        can only start when the engine is empty (its left-pad would
        rewind other rows' history)."""
        with _phase(self.profile, "admission"):
            return self._admit_inner(pending)

    def _admit_inner(self, pending):
        import jax
        import jax.numpy as jnp
        import numpy as _np
        if self.paged:
            if self._qos_gate is not None:
                # requests whose token bucket refilled since they were
                # throttled at submit() enter the queue ahead of this
                # call's batch (they arrived first)
                for req in self._qos_gate.release():
                    self._sched.add(req)
            while pending:
                self._sched.add(pending.pop(0))
            return self._admit_scheduled()
        if self.idle() and pending:
            # fresh fill: size it to the whole first wave so a longer
            # second prompt is not head-of-line deferred behind a
            # shorter first one
            wave = [r.ids.reshape(-1).size
                    for r in pending[:self.capacity]]
            fits = [n for n in wave if n <= self.s_max - self.chunk]
            if fits:
                self._g = max(self._g, max(fits))
        for slot in range(self.capacity):
            if self._rows[slot] is not None or not pending:
                continue
            n = pending[0].ids.reshape(-1).size
            if n > self.s_max - self.chunk:
                req = pending.pop(0)
                self._fail_request(req, ValueError(
                    f"prompt of {n} tokens exceeds engine s_max="
                    f"{self.s_max}"))
                continue
            if n > self._g:
                if not self.idle():
                    break               # wait for the fill to reach n
                self._g = n
            req = pending.pop(0)
            self._trace_admission(req)
            try:
                ids = _np.full((1, self.s_max), self.pad_id, _np.int32)
                prompt = req.ids.reshape(-1).astype(_np.int32)
                ids[0, self._g - n:self._g] = prompt
                pad = self._g - n
                st, embed, fnorm, lm = self._weights()
                with RecordEvent("engine.prefill", "engine", worker=self.worker_id):
                    first, ks, vs = self._prefill(
                        st, embed, fnorm, lm, self._scales,
                        jnp.asarray(ids), jnp.asarray([pad], jnp.int32),
                        self._g)
            except Exception as e:  # noqa: BLE001 — fail THIS request,
                self._fail_request(req, e)  # not the whole engine
                continue
            self.prefills += 1
            self._c_prefills.inc()
            self._c_device_calls.inc()
            self._c_admitted.inc()
            # insert this row's lane: [L, 1, sc, kvh, hd] -> slot
            self._ck = jax.lax.dynamic_update_slice(
                self._ck, ks.astype(self._ck.dtype), (0, slot, 0, 0, 0))
            self._cv = jax.lax.dynamic_update_slice(
                self._cv, vs.astype(self._cv.dtype), (0, slot, 0, 0, 0))
            self._pad[slot] = pad
            first_tok = int(first[0])
            self._tok[slot] = first_tok
            self._observe_first_token(req)
            self._rows[slot] = {"req": req, "prompt": prompt,
                                "toks": [first_tok]}

    # -- paged admission: scheduler + prefix cache + preemption -------------
    @staticmethod
    def _prio(req) -> int:
        return int(getattr(req, "priority", 0) or 0)

    def _fail_request(self, req, err):
        req.error = err
        req.event.set()
        self._c_failed.inc()
        tr = getattr(req, "trace", None)
        _tmark(req, "failed", worker=self.worker_id)
        log_kv(_log, "request_failed", level=logging.WARNING,
               worker=self.worker_id,
               req=tr.request_id if tr is not None else None,
               error=type(err).__name__, detail=str(err))

    def _qos_charge(self, req, tokens):
        """Advance the request's tenant's fair-share virtual time
        (ISSUE 6). No-op without QoS — the plain scheduler has no
        ``charge`` and ``qos`` is None."""
        if self.qos is None or tokens <= 0:
            return
        from .qos import tenant_of
        self._sched.charge(tenant_of(req), tokens)

    def submit(self, input_ids, max_new_tokens=32, priority=0,
               tenant=None):
        """Validated single-request entry point (ISSUE 6): builds the
        ``_Request`` (raising ``ValueError`` on an empty prompt or a
        non-positive token budget), runs tenant admission when a
        ``qos=`` policy was configured, and enqueues into the paged
        scheduler. Returns the request handle; a rejected request has
        ``error`` set and its ``wait()`` raises immediately. Throttled
        requests sit behind their token bucket and enter the queue on a
        later :meth:`admit` once the bucket refills."""
        if not self.paged:
            raise RuntimeError(
                "submit() requires the paged engine; pass request "
                "lists to admit() in contiguous mode")
        req = _Request(input_ids, max_new_tokens, priority=priority,
                       tenant=tenant)
        if self._qos_gate is not None:
            verdict, reason = self._qos_gate.decide(req)
            if verdict == "reject":
                tr = getattr(req, "trace", None)
                if tr is not None:
                    tr.set_attr("reject_reason", reason)
                self._fail_request(req, PermissionError(
                    f"QoS rejected ({reason}) for tenant "
                    f"{tenant!r}"))
                return req
            if verdict == "throttle":
                _tmark(req, "queued")   # gate wait counts as queue wait
                return req
        self._sched.add(req)
        return req

    def _pick_victim(self, prio, exclude=None):
        """Slot of the running row to preempt for a priority-``prio``
        claimant: STRICTLY lower priority only (equal priorities wait
        instead — no preemption cycles), lowest priority first, newest
        arrival first among equals. None when no row qualifies."""
        best = None
        for slot, row in enumerate(self._rows):
            if row is None or slot == exclude:
                continue
            p = self._prio(row["req"])
            if p >= prio:
                continue
            if best is None or (p, -row["req"]._sched_seq) < \
                    (self._prio(self._rows[best]["req"]),
                     -self._rows[best]["req"]._sched_seq):
                best = slot
        return best

    def _release_row_pages(self, row):
        """Drop the row's reference on every page it maps (shared prefix
        pages survive under the cache's/other rows' references; private
        pages return to the free list)."""
        for p in row["pages"]:
            self._alloc.decref(p)

    def _release_slot_state(self, slot):
        """A stateful family's slot gives its state up with its row: the
        arrays keep the bytes, which the decode program no longer reads
        (``lens`` 0) and the slot's next prefill overwrites. A preempted
        request takes nothing along: it resumes by recomputing its
        prefill."""
        if slot in self._state_slots:
            with RecordEvent("engine.state_release", "engine",
                             worker=self.worker_id):
                self._state_slots.discard(slot)

    def _cached_seq(self, row):
        """The token sequence whose KV is resident for the row right
        now: prompt plus all emitted tokens except the last (the last
        token is the next decode input — its KV is written by the next
        step). Length == lens[slot] by the engine invariant."""
        import numpy as _np
        return _np.concatenate(
            [row["prompt"],
             _np.asarray(row["toks"][:-1], _np.int32)]) \
            if len(row["toks"]) > 1 else row["prompt"]

    def _preempt_row(self, slot):
        """Evict a running row for recompute-resume: publish its
        resident prefix to the cache (kept as cached prefix — eviction
        reclaims it page-by-page only as the pool actually needs),
        release the row's references, and re-queue the request with its
        emitted tokens so resumption is lossless."""
        bs = self.block_size
        row = self._rows[slot]
        req = row["req"]
        if not row["toks"] and "pf_seq" not in row:
            self._settle(early=True)    # its first token is still unread
            if self._rows[slot] is not row:
                return                  # the read failed the row
        with RecordEvent("engine.preempt", "engine", worker=self.worker_id):
            if "pf_seq" in row:
                # mid-prefill victim (ISSUE 7): publish only COMPLETED
                # pages — the partial page's tail is still unwritten.
                # The request re-queues with its pre-preemption resume
                # tokens (None for a fresh prompt) and re-prefills via
                # the r7 recompute path, re-matching what was published.
                valid = int(row["pf_pos"])
                full = (valid // bs) * bs
                if self._cache is not None and full > 0:
                    self._cache.insert(row["pf_seq"][:full],
                                       row["pages"][:full // bs])
                req._resume_toks = row["pf_resume"]
            else:
                valid = int(self._lens[slot])
                if self._cache is not None and valid > 0:
                    seq = self._cached_seq(row)[:valid]
                    self._cache.insert(seq,
                                       row["pages"][:-(-valid // bs)])
                req._resume_toks = list(row["toks"])
            self._release_row_pages(row)
            self._release_slot_state(slot)
            self._c_preempted.inc()
            _tmark(req, "preempted", worker=self.worker_id)
            self._tables[slot] = 0
            self._lens[slot] = 0
            self._rows[slot] = None
            self._sched.add(req)
        tr = getattr(req, "trace", None)
        log_kv(_log, "preempted", level=logging.DEBUG,
               worker=self.worker_id,
               req=tr.request_id if tr is not None else None,
               slot=slot, resident_tokens=valid,
               emitted=len(req._resume_toks or []))

    def _reclaim_allocate(self, need, prio, exclude=None,
                          claimant=None, start_col=0):
        """allocate() with reclamation: evict unreferenced cached pages
        first, then preempt strictly-lower-priority rows (each
        preemption parks its pages in the cache, so the follow-up evict
        actually frees them). None when the pool still can't cover
        ``need``. ``claimant`` is the request driving the reclamation —
        under fair-share QoS the PREEMPTING tenant is charged the
        victim's resident tokens, so a tenant cannot launder work
        through evictions (ISSUE 6). ``start_col`` is the block-table
        column the first page will occupy — striped allocators (2-D
        mesh) pick stripes from it to keep column j in stripe
        j % seq."""
        pages = self._alloc.allocate(need, start_col)
        if pages is not None:
            return pages
        if self._cache is not None:
            pages = self._evict_allocate(need, start_col)
            if pages is not None:
                return pages
        while True:
            victim = self._pick_victim(prio, exclude=exclude)
            if victim is None:
                return None
            vrow = self._rows[victim]
            evicted_tokens = int(vrow["pf_pos"]) if "pf_seq" in vrow \
                else int(self._lens[victim])
            self._preempt_row(victim)
            if claimant is not None:
                self._qos_charge(claimant, evicted_tokens)
            if self._cache is not None:
                pages = self._evict_allocate(need, start_col)
            else:
                pages = self._alloc.allocate(need, start_col)
            if pages is not None:
                return pages

    def _evict_allocate(self, need, start_col=0):
        """Evict cached pages, then allocate — repeating while eviction
        still frees something. One round suffices for an unstriped pool
        (and stripes=1 keeps the single-round r14 behavior exactly),
        but the LRU evictor frees pages by AGE, not by stripe, so a
        striped pool may need several rounds before the starved
        stripe's cached pages finally drain."""
        while True:
            freed = self._evict_cached(
                self._alloc.shortfall(need, start_col))
            pages = self._alloc.allocate(need, start_col)
            if pages is not None or not freed \
                    or self._alloc.stripes == 1:
                return pages

    def _evict_cached(self, n):
        """Cache eviction under a timeline span (the unified trace
        shows WHEN pool pressure forced reclamation)."""
        with RecordEvent("engine.evict", "engine", worker=self.worker_id):
            freed = self._cache.evict(n)
        if freed:
            log_kv(_log, "cache_evicted", level=logging.DEBUG,
                   pages=freed, pool_free=self._alloc.num_free)
        return freed

    def _admit_scheduled(self):
        import numpy as _np
        bs = self.block_size
        while self._sched:
            slot = next((i for i, r in enumerate(self._rows)
                         if r is None), None)
            if slot is None:
                return              # no slot: wait for a retire
            req = self._sched.peek()
            prompt = req.ids.reshape(-1).astype(_np.int32)
            n = prompt.size
            if n > self.s_max - self.chunk:
                self._sched.pop()
                self._fail_request(req, ValueError(
                    f"prompt of {n} tokens exceeds engine s_max="
                    f"{self.s_max}"))
                continue
            resume = getattr(req, "_resume_toks", None)
            # the sequence that must be KV-resident before decode runs:
            # prompt + emitted tokens minus the last (= the next input)
            seq = prompt if not resume else _np.concatenate(
                [prompt, _np.asarray(resume[:-1], _np.int32)])
            ns = seq.size
            total_need = -(-ns // bs)
            m = self._cache.match(seq, ns - 1) \
                if self._cache is not None else None
            f = len(m.pages) if m is not None else 0
            pages = self._reclaim_allocate(total_need - f,
                                           self._prio(req),
                                           claimant=req, start_col=f)
            if pages is None and m is not None and m.cached_len:
                # the match's own references pin otherwise-evictable
                # pages: retry COLD so the infeasibility test below is
                # exact
                self._cache.release(m)
                m, f = None, 0
                pages = self._reclaim_allocate(total_need,
                                               self._prio(req),
                                               claimant=req)
            if pages is None:
                if m is not None:
                    self._cache.release(m)
                if self._no_rows():
                    # nothing left to retire/evict/preempt — the pool
                    # genuinely cannot hold this request
                    self._sched.pop()
                    self._fail_request(req, RuntimeError(
                        f"prompt needs {total_need} pages but the pool "
                        f"holds {self._alloc.capacity} "
                        f"(n_blocks={self.n_blocks}, bs={bs})"))
                    continue
                return          # wait: running rows will free pages
            self._sched.pop()
            self._trace_admission(req)
            # snapshot BEFORE the prefill: release_cow inside it zeroes
            # the match's cow_len, which would undercount the hit
            hit_tokens = m.cached_len if m is not None else 0
            if self.chunked_prefill:
                try:
                    self._begin_chunked_prefill(slot, req, prompt, seq,
                                                m, pages, resume,
                                                hit_tokens)
                except Exception as e:  # noqa: BLE001 — fail THIS
                    if m is not None:   # request, not the whole engine
                        self._cache.release(m)
                    self._alloc.free(pages)
                    self._fail_request(req, e)
                continue
            try:
                first = self._prefill_row(
                    slot, seq, m, pages, resume[-1] if resume else None)
            except Exception as e:  # noqa: BLE001 — fail THIS request,
                if m is not None:   # not the whole engine
                    self._cache.release(m)
                self._alloc.free(pages)
                self._fail_request(req, e)
                continue
            all_pages = (m.pages if m is not None else []) + pages
            # the first token stays unread (``_settle``): what follows
            # needs the row's length and pages, never the token's value
            toks = list(resume) if resume else []
            req._resume_toks = None
            self.prefills += 1
            self._c_prefills.inc()
            self._c_admitted.inc()
            self._c_prefix_hit.inc(hit_tokens)
            # fair-share: admission costs the tenant only the UNCACHED
            # suffix it actually prefilled (prefix hits are free, same
            # as the page-charging rule)
            self._qos_charge(req, ns - hit_tokens)
            tr = getattr(req, "trace", None)
            log_kv(_log, "admitted", level=logging.DEBUG,
                   worker=self.worker_id,
                   req=tr.request_id if tr is not None else None,
                   slot=slot, tokens=int(ns), cached_tokens=hit_tokens,
                   pages=len(all_pages), resumed=bool(resume))
            self._lens[slot] = ns
            self._rows[slot] = row = {"req": req, "prompt": prompt,
                                      "toks": toks, "pages": all_pages}
            if resume:
                self._observe_first_token(req)  # made before, as a rule
            else:
                self._unread.append((first, slot, row))

    def _prefill_row(self, slot, seq, m, pages, known=None):
        """Launch the admission prefill for ``seq`` into ``pages`` (plus
        the match's shared pages), seeding the slot's block table.
        Cold (no cached prefix): the blockwise program over the
        window's live blocks.
        Prefix hit: COW-copy the partially-shared page if any, then the
        position-offset tail prefill over a bucketed window. Nothing is
        read back: the argmax token at the last real position (or
        ``known``, a resumed row's next input) goes into the device's
        token vector at ``slot``, and the ``[1]`` array that holds it is
        returned, its copy to the host asked for."""
        with RecordEvent("engine.prefill", "engine", worker=self.worker_id):
            return self._prefill_row_inner(slot, seq, m, pages, known)

    def _prefill_row_inner(self, slot, seq, m, pages, known):
        import numpy as _np
        bs = self.block_size
        ns = seq.size
        cached = m.cached_len if m is not None else 0
        table_row = _np.zeros((self._max_blocks,), _np.int32)
        allp = (m.pages if m is not None else []) + pages
        table_row[:len(allp)] = allp
        st, embed, fnorm, lm = self._weights()
        self._drain_scale_resets()
        if cached == 0:
            ids = _np.full((1, self.s_max), self.pad_id, _np.int32)
            ids[0, self.s_max - ns:] = seq
            pad = self.s_max - ns
            blocks = -(-ns // self._prefill_block)
            launch = self._launch_args("prefill", blocks, 1, ns)
            t0 = _now()
            with _phase(self.profile, "launch", launch):
                where = (table_row,)
                if self._state_specs:
                    where += (_np.int32(slot),)
                args = (st, embed, fnorm, lm, self._scales, ids,
                        _np.array([pad], _np.int32), *where, *self._pool())
                avals = self._scope_avals("jit_prefill_paged", args)
                first, *pool = self._prefill(*args)
                self._set_pool(pool)
                self._hand_on(slot, first, known)
                if self._state_specs:
                    # the program started the row's states from zero
                    # and left them in ``slot``: whatever the slot's
                    # last tenant left there was overwritten, never read
                    with RecordEvent("engine.state_admit", "engine",
                                     worker=self.worker_id):
                        self._state_slots.add(slot)
            self._note_launch(t0, launch)
            self._note_scopes("jit_prefill_paged", self._prefill, avals)
            self._c_device_calls.inc()
            self._c_prefill_blocks.inc(blocks)
            self._c_ssm_prefill_chunks.inc(
                blocks * self._progs.chunks_per_block)
            self._c_prefill_window_blocks.inc(
                -(-self.s_max // self._prefill_block))
        else:
            if m.cow_src is not None:
                # private copy of the partially-shared page: the tail's
                # first write lands mid-page at position ``cached``
                with _phase(self.profile, "launch"):
                    self._set_pool(self._cow(
                        _np.int32(m.cow_src), _np.int32(pages[0]),
                        *self._pool()))
                self._cache.release_cow(m)
                self._c_device_calls.inc()
            tail = seq[cached:]
            sc = self._bucket_window(tail.size)
            ids = _np.full((1, sc), self.pad_id, _np.int32)
            ids[0, sc - tail.size:] = tail
            pad = sc - tail.size
            prefill_prefix = self._prefix_prefill_for(sc)
            with _phase(self.profile, "launch"):
                first, *pool = prefill_prefix(
                    st, embed, fnorm, lm, self._scales, ids,
                    _np.array([pad], _np.int32),
                    _np.array([cached], _np.int32), table_row,
                    *self._pool())
                self._set_pool(pool)
                self._hand_on(slot, first, known)
            self._c_device_calls.inc()
        self._c_launch_ahead.inc()
        self._tables[slot] = table_row
        return first

    # -- chunked prefill (ISSUE 7 tentpole) ---------------------------------
    def _begin_chunked_prefill(self, slot, req, prompt, seq, m, pages,
                               resume, hit_tokens):
        """Chunked admission: take the slot and the pages (and COW-copy
        the partially-shared prefix page) NOW, but defer the prompt
        forward — decode_once() feeds page-sized chunks through the
        bucketed position-offset prefill under the step budget. The
        row keeps its block table PRIVATE until the last chunk lands:
        ``self._tables[slot]`` stays all-NULL, so the decode program's
        writes for this lane route to the NULL page instead of
        clobbering chunk-scattered K/V."""
        import numpy as _np
        cached = m.cached_len if m is not None else 0
        self._drain_scale_resets()      # before COW: keep copied scales
        if m is not None and m.cow_src is not None:
            with RecordEvent("engine.prefill", "engine",
                             worker=self.worker_id):
                self._set_pool(self._cow(
                    _np.int32(m.cow_src), _np.int32(pages[0]),
                    *self._pool()))
            self._cache.release_cow(m)
            self._c_device_calls.inc()
        all_pages = (m.pages if m is not None else []) + pages
        table_row = _np.zeros((self._max_blocks,), _np.int32)
        table_row[:len(all_pages)] = all_pages
        req._resume_toks = None
        self._c_admitted.inc()
        self._c_prefix_hit.inc(hit_tokens)
        tr = getattr(req, "trace", None)
        log_kv(_log, "admitted", level=logging.DEBUG,
               worker=self.worker_id,
               req=tr.request_id if tr is not None else None,
               slot=slot, tokens=int(seq.size), cached_tokens=hit_tokens,
               pages=len(all_pages), resumed=bool(resume), chunked=True)
        self._rows[slot] = {"req": req, "prompt": prompt, "toks": [],
                            "pages": all_pages,
                            "pf_seq": seq,          # full resident goal
                            "pf_pos": cached,       # tokens scattered
                            "pf_table": table_row,  # private until done
                            "pf_resume": list(resume) if resume
                            else None}

    def _run_prefill_chunks(self, budget):
        """Spend the step budget's remainder on prefill chunks: the
        scheduler orders the candidates (priority/FCFS, or fair-share
        vtime under QoS) and funds whole chunks; each funded chunk runs
        the bucketed position-offset prefill and scatters one window of
        K/V. The chunk that completes the prompt emits the first token
        and installs the row into the decode batch."""
        slots = {}
        cands = []
        for slot, row in enumerate(self._rows):
            if row is None or "pf_seq" not in row:
                continue
            take = min(self.prefill_chunk,
                       row["pf_seq"].size - row["pf_pos"])
            cands.append((row["req"], take))
            slots[id(row["req"])] = slot
        if not cands:
            return
        for req, take in self._sched.plan_prefill(budget, cands):
            slot = slots[id(req)]
            try:
                self._prefill_chunk_row(slot, self._rows[slot], take)
            except Exception as e:  # noqa: BLE001 — fail THIS request,
                self._fail_row_paged(slot, e)  # not the whole engine

    def _prefill_chunk_row(self, slot, row, take):
        """One funded chunk: ``take`` prompt tokens through the r7
        position-offset tail program (prefix_len = tokens already
        resident, cold first chunks run it with prefix_len=0), K/V
        scattered at the offset. Windows bucket through
        ``_bucket_window`` — with the default page-sized chunk every
        window is the 16-slot bucket, one already-documented shape."""
        import numpy as _np
        req = row["req"]
        seq, pos = row["pf_seq"], int(row["pf_pos"])
        tail = seq[pos:pos + take]
        sc = self._bucket_window(tail.size)
        ids = _np.full((1, sc), self.pad_id, _np.int32)
        ids[0, sc - tail.size:] = tail
        pad = sc - tail.size
        st, embed, fnorm, lm = self._weights()
        self._drain_scale_resets()
        with _phase(self.profile, "prefill_chunk"), \
                RecordEvent("engine.prefill_chunk", "engine",
                            worker=self.worker_id):
            first, *pool = self._prefix_prefill_for(sc)(
                st, embed, fnorm, lm, self._scales, ids,
                _np.array([pad], _np.int32), _np.array([pos], _np.int32),
                row["pf_table"], *self._pool())
            self._set_pool(pool)
        self._c_device_calls.inc()
        row["pf_pos"] = pos + tail.size
        self._c_prefill_chunks.inc()
        _tmark(req, "prefill_chunk", worker=self.worker_id)
        # fair-share: the tenant pays for each chunk AS IT RUNS, not
        # the whole uncached suffix at admission — a long prompt's
        # vtime advances per-step, rotating its chunks with other
        # tenants' work
        self._qos_charge(req, tail.size)
        if row["pf_pos"] >= seq.size:
            # last chunk: its last-real-position logits ARE the prompt
            # logits — first-token emission, table install, decode from
            # the next program on. The funding of this very step reads
            # the row's tokens, so the read is made now (today's order)
            resume = row.pop("pf_resume")
            self._hand_on(slot, first, resume[-1] if resume else None)
            self._tables[slot] = row.pop("pf_table")
            self._lens[slot] = seq.size
            row["toks"] = list(resume) if resume else []
            del row["pf_seq"], row["pf_pos"]
            self.prefills += 1
            self._c_prefills.inc()
            if resume:
                self._observe_first_token(req)
            else:
                self._unread.append((first, slot, row))
                self._settle(early=True)

    def decode_once(self):
        """Run ONE bounded decode chunk, collect tokens, retire finished
        rows (their futures resolve immediately). Returns the number of
        still-alive rows."""
        prof = self.profile
        if prof is None:
            return self._decode_once_inner()
        prof.begin_step()
        try:
            return self._decode_once_inner()
        finally:
            prof.end_step()

    def _decode_once_inner(self):
        import jax.numpy as jnp
        import numpy as _np
        if self.idle():
            return 0
        if self.paged:
            if self.mesh is not None and (
                    self.spec_decode
                    or (self.chunked_prefill
                        and any(r is not None and "pf_seq" in r
                                for r in self._rows))):
                # ISSUE 10: sharded engines collapse verify windows and
                # prefill chunks into ONE mixed launch per step. Plain
                # decode with no mid-prefill rows keeps the chunk-scan
                # program (chunk tokens per launch beats one).
                return self._decode_once_mixed()
            if self.spec_decode:
                return self._decode_once_spec()
            return self._decode_once_paged()
        with _phase(self.profile, "prepare"):
            steps = self.chunk
            if self._g + steps > self.s_max:
                # cache exhaustion: fail ONLY rows whose remaining demand
                # cannot fit in the leftover fill; survivors ride one final
                # CLAMPED chunk out instead of getting the exhaustion error
                space = self.s_max - self._g
                for slot, row in enumerate(self._rows):
                    if row is None:
                        continue
                    need = row["req"].max_new - len(row["toks"])
                    if need > space:
                        self._fail_request(row["req"], RuntimeError(
                            f"engine cache exhausted at fill {self._g} "
                            f"(s_max={self.s_max}): {need} tokens still "
                            f"needed, {space} slots left"))
                        self._rows[slot] = None
                if space <= 0 or self.idle():
                    self._reset()  # a wedged fill must not brick later
                    return 0       # bursts
                steps = space      # every survivor finishes inside it
            st, embed, fnorm, lm = self._weights()
        t0 = _now()                # decode-only window: admit()'s
        #                            prefill/compile must not read as a
        #                            phantom throughput collapse
        with RecordEvent("engine.decode_chunk", "engine", worker=self.worker_id):
            with _phase(self.profile, "launch"):
                toks, self._ck, self._cv = self._decode_for(steps)(
                    st, embed, fnorm, lm, self._scales,
                    jnp.asarray(self._tok), self._ck, self._cv,
                    self._g, jnp.asarray(self._pad))
            with _phase(self.profile, "host_sync"):
                toks = _np.asarray(toks)   # [steps, B] (fetch = sync)
        with _phase(self.profile, "account"):
            wall = _now() - t0
            self._g += steps
            self.device_steps += steps
            self._c_steps.inc(steps)
            self._c_device_calls.inc()
            self._h_chunk.observe(wall)
            n_busy = sum(r is not None for r in self._rows)
            self._g_occupancy.set(n_busy)
            log_event("engine_chunk", steps=steps, rows=n_busy,
                      fill=self._g, wall_s=round(wall, 4),
                      tokens_per_s=round(steps * n_busy
                                         / max(wall, 1e-9), 1))
        alive = 0
        with _phase(self.profile, "publish"):
            for slot, row in enumerate(self._rows):
                if row is None:
                    continue
                emitted_before = len(row["toks"])
                row["toks"].extend(int(t) for t in toks[:, slot])
                self._tok[slot] = int(toks[-1, slot])
                req = row["req"]
                _tmark(req, "decode_chunk", worker=self.worker_id,
                       n_tokens=min(steps,
                                    req.max_new - emitted_before))
                if len(row["toks"]) >= req.max_new:
                    req.result = _np.concatenate(
                        [row["prompt"],
                         _np.asarray(row["toks"][:req.max_new],
                                     _np.int32)])
                    self._observe_retired(req)
                    req.event.set()
                    self._rows[slot] = None  # slot free for next admit
                else:
                    alive += 1
        if alive == 0 and self.idle():
            self._reset()                # fresh fill for the next burst
        return alive

    # -- paged engine loop --------------------------------------------------
    def _retire_paged(self, slot, publish=True):
        """Release the row's page references and clear its lane. On a
        clean retire the row's now-immutable prefix (prompt + generated
        tokens whose KV is resident) is PUBLISHED to the prefix cache
        first, so an identical re-submission allocates zero new pages
        for it; failed rows release without publishing."""
        import numpy as _np
        row = self._rows[slot]
        if publish and self._cache is not None:
            req = row["req"]
            valid = row["prompt"].size + req.max_new - 1
            seq = _np.concatenate(
                [row["prompt"],
                 _np.asarray(row["toks"][:req.max_new - 1], _np.int32)])
            self._cache.insert(seq, row["pages"][:-(-valid //
                                                    self.block_size)])
        if publish:
            self._observe_retired(row["req"])
        self._release_row_pages(row)
        self._release_slot_state(slot)
        self._tables[slot] = 0          # all-NULL: inactive lane
        self._lens[slot] = 0
        self._rows[slot] = None

    def _fail_row_paged(self, slot, err):
        row = self._rows[slot]
        self._fail_request(row["req"], err)
        self._retire_paged(slot, publish=False)

    def _step_budget(self):
        """This step's token budget, pre-charged with migration debt:
        tokens transplanted INTO this engine since the last step (r19)
        were KV bandwidth spent on this engine's behalf, so they claim
        budget force-side before decode lanes and prefill chunks see
        the remainder. Zero debt — the default, and always when fleet
        migration is off — builds the identical r12 budget."""
        from .scheduler import StepBudget
        budget = StepBudget(self.step_budget)
        if self._mig_debt:
            budget.take(self._mig_debt, force=True)
            self._mig_debt = 0
        return budget

    def _prepare_decode_paged(self):
        """The decode prologue up to the page tables: fund and run this
        step's prefill chunks (chunked prefill), then grow each live
        row's page list to cover the chunk's writes. Returns None when
        decode lanes are ready to launch, else what ``decode_once``
        returns without one (the rows alive)."""
        bs = self.block_size
        if self.chunked_prefill:
            # ISSUE 7: one mixed step. Decode lanes claim their tokens
            # FIRST (decode is never throttled), then the scheduler
            # funds prefill chunks out of the remainder. A row whose
            # last chunk lands joins THIS step's decode program — its
            # tokens are claimed force-side so the budget histogram
            # reflects the step's real load.
            budget = self._step_budget()
            pre = set()
            for slot, row in enumerate(self._rows):
                if row is not None and "pf_seq" not in row:
                    pre.add(slot)
                    budget.take(min(self.chunk, row["req"].max_new
                                    - len(row["toks"])), force=True)
            self._run_prefill_chunks(budget)
            for slot, row in enumerate(self._rows):
                if row is not None and "pf_seq" not in row \
                        and slot not in pre:
                    budget.take(min(self.chunk, row["req"].max_new
                                    - len(row["toks"])), force=True)
            self._h_budget.observe(budget.used)
            if not any(r is not None and "pf_seq" not in r
                       for r in self._rows):
                # every live row is still mid-prefill: no decode lanes
                # this step (running the decode program would only
                # scribble on the NULL page)
                return sum(r is not None for r in self._rows)
        # grow each live row's page list to cover this chunk's writes.
        # Ascending extra-page need: a starved row's freed pages rescue
        # the rows processed after it, so one hungry row never drags
        # innocents into the exhaustion error. Mid-prefill rows never
        # grow — admission sized their pages for the whole prompt.
        grow = []
        for slot, row in enumerate(self._rows):
            if row is None or "pf_seq" in row:
                continue
            # a row whose first token is still unread has emitted one
            use = min(self.chunk,
                      row["req"].max_new - max(1, len(row["toks"])))
            target = int(self._lens[slot]) + use
            grow.append((slot, row, target,
                         -(-target // bs) - len(row["pages"])))
        for slot, row, target, extra in sorted(grow,
                                               key=lambda t: t[3]):
            if self._rows[slot] is not row:
                continue                # preempted by an earlier claim
            if target > self.s_max:
                self._fail_row_paged(slot, RuntimeError(
                    f"row exceeds engine s_max={self.s_max} at length "
                    f"{int(self._lens[slot])}"))
                continue
            if extra <= 0:
                continue
            pages = self._reclaim_allocate(extra, self._prio(row["req"]),
                                           exclude=slot,
                                           claimant=row["req"],
                                           start_col=len(row["pages"]))
            if pages is None and self.chunked_prefill:
                # a decode-complete row's growth outranks equal-or-
                # lower-priority rows still MID-prefill: they lose the
                # least work and resume losslessly. Without this a tiny
                # pool livelocks — the grower self-preempts, re-admits,
                # re-prefills, and self-preempts again while the
                # mid-prefill row it starves never retires a page.
                my_p = self._prio(row["req"])
                pf = [i for i, r in enumerate(self._rows)
                      if r is not None and i != slot and "pf_seq" in r
                      and self._prio(r["req"]) <= my_p]
                pf.sort(key=lambda i:            # newest arrival first
                        -self._rows[i]["req"]._sched_seq)
                while pages is None and pf:
                    v = pf.pop(0)
                    evicted = int(self._rows[v]["pf_pos"])
                    self._preempt_row(v)
                    self._qos_charge(row["req"], evicted)
                    if self._cache is not None:
                        self._evict_cached(self._alloc.shortfall(
                            extra, len(row["pages"])))
                    pages = self._alloc.allocate(
                        extra, len(row["pages"]))
            if pages is None:
                others = any(r is not None and i != slot
                             for i, r in enumerate(self._rows))
                if others and self._cache is not None:
                    # lossless self-preemption: park this row's prefix
                    # in the cache and re-queue it — it resumes when the
                    # survivors retire, instead of erroring out
                    self._preempt_row(slot)
                    continue
                self._fail_row_paged(slot, RuntimeError(
                    f"paged KV pool exhausted: needed {extra} more "
                    f"pages, {self._alloc.num_free} free "
                    f"(n_blocks={self.n_blocks}, bs={bs})"))
                continue
            start = len(row["pages"])
            row["pages"] = row["pages"] + pages
            self._tables[slot, start:start + extra] = pages
        if self._no_rows():
            return 0
        if self.chunked_prefill and not any(
                r is not None and "pf_seq" not in r for r in self._rows):
            return sum(r is not None for r in self._rows)
        return None

    def _decode_once_paged(self):
        import numpy as _np
        with _phase(self.profile, "prepare"):
            alive = self._prepare_decode_paged()
            if alive is not None:
                self._settle()          # of rows that growth just failed
                return alive            # no decode lanes this step
            st, embed, fnorm, lm = self._weights()
            self._drain_scale_resets()
            n_busy = sum(r is not None for r in self._rows)
            # a live row's context grows by one a step of the chunk
            ctx_tokens = self.chunk * int(self._lens.sum()) \
                + n_busy * self.chunk * (self.chunk - 1) // 2
            launch = self._launch_args("decode", self.chunk, n_busy,
                                       ctx_tokens)
            if self._c_host:    # [steps, live rows], before the host
                ctx_rows = (    # goes on writing ``_lens``
                    self._lens[self._lens > 0].astype(_np.int64)[None, :]
                    + _np.arange(self.chunk)[:, None])
        t0 = _now()
        with RecordEvent("engine.decode_chunk", "engine", worker=self.worker_id):
            with _phase(self.profile, "launch", launch):
                if self._unread:
                    self._c_launch_ahead.inc()
                # copies: the host goes on writing both while the
                # launch is in flight
                args = (st, embed, fnorm, lm, self._scales, self._tok,
                        self._tables.copy(), self._lens.copy(),
                        *self._pool())
                avals = self._scope_avals("jit_decode_chunk_paged", args)
                toks, *pool = self._decode(*args)
                self._set_pool(pool)
                toks.copy_to_host_async()
                self._tok = self._tok_last(toks)
            # only now does the host read, in launch order: the first
            # token of each prefill left unread, then the chunk
            self._settle()
            with _phase(self.profile, "host_sync"):
                toks = self._fetch(toks)   # [chunk, B] (fetch = sync)
        with _phase(self.profile, "account"):
            wall = _now() - t0
            self.device_steps += self.chunk
            self._c_steps.inc(self.chunk)
            self._c_device_calls.inc()
            self._h_chunk.observe(wall)
            self._g_occupancy.set(n_busy)
            self._c_decode_row_steps.inc(self.chunk * n_busy)
            self._c_decode_ctx.inc(ctx_tokens)
            for fn, c in self._c_host:
                c.inc(fn(ctx_rows))
            self._note_launch(t0, launch)
            self._note_scopes("jit_decode_chunk_paged", self._decode, avals)
            if self._progs.chunks_per_block:
                self._c_ssm_row_steps.inc(self.chunk * n_busy)
            log_event("engine_chunk", steps=self.chunk, rows=n_busy,
                      fill=int(self._lens.max()), wall_s=round(wall, 4),
                      tokens_per_s=round(self.chunk * n_busy
                                         / max(wall, 1e-9), 1),
                      blocks_used=self._alloc.num_used,
                      blocks_free=self._alloc.num_free)
        alive = 0
        with _phase(self.profile, "publish"):
            for slot, row in enumerate(self._rows):
                if row is None:
                    continue
                if "pf_seq" in row:
                    alive += 1      # mid-prefill: alive, not decoding
                    continue        # (its lane wrote to the NULL page)
                emitted_before = len(row["toks"])
                row["toks"].extend(toks[:, slot].tolist())
                req = row["req"]
                useful = min(self.chunk, req.max_new - emitted_before)
                _tmark(req, "decode_chunk", worker=self.worker_id,
                       n_tokens=useful)
                # fair-share: the tenant pays for the USEFUL tokens
                # this chunk produced (overshoot past max_new is
                # engine padding, not tenant work)
                self._qos_charge(req, useful)
                if len(row["toks"]) >= req.max_new:
                    req.result = _np.concatenate(
                        [row["prompt"],
                         _np.asarray(row["toks"][:req.max_new],
                                     _np.int32)])
                    self._retire_paged(slot)  # pages free to re-admit
                    req.event.set()
                    if self.qos is not None:
                        from .qos import tenant_of
                        self.qos.note_served(tenant_of(req),
                                             req.max_new)
                else:
                    self._lens[slot] += self.chunk
                    alive += 1
        return alive

    # -- self-speculative decoding (ISSUE 8 tentpole) -----------------------
    def _draft_for(self, slot, row):
        """Draft tokens for one decode-ready row from its OWN history
        (prompt + emitted tokens, the last being the pending next
        input), clamped so the verify step can never emit past the
        request's max_new (at most k+1 emissions) nor write KV past
        s_max (k+1 writes at positions lens..lens+k)."""
        import numpy as _np
        req = row["req"]
        limit = min(req.max_new - len(row["toks"]) - 1,
                    self.s_max - int(self._lens[slot]) - 1)
        if limit <= 0:
            return _np.zeros((0,), _np.int32)
        ctx = _np.concatenate(
            [row["prompt"], _np.asarray(row["toks"], _np.int32)])
        with _phase(self.profile, "spec_draft"):
            return self._drafter.propose(ctx, limit=limit)

    def _decode_once_spec(self):
        """One SPECULATIVE engine step (ISSUE 8 tentpole): every
        decode-ready row drafts k tokens from its own history, verifies
        all of them in ONE bucketed position-offset prefill (the
        pending token + drafts at ``prefix_len = tokens-resident``) and
        accepts the longest argmax-matching prefix — 1..k+1 tokens per
        row per step, bit-identical to plain greedy decode because
        every accepted token IS the verify program's argmax. Rejected
        drafts roll back implicitly: ``lens`` advances only past the
        accepted positions, so their stale KV is masked out and simply
        re-written when the cursor reaches them (no COW churn).

        Chunked-prefill interplay mirrors _decode_once_paged: decode
        lanes force-charge their verify tokens (k+1 — the step budget
        pays for PROPOSED work) first, prefill chunks spend the
        remainder, and rows whose last chunk lands this step verify
        too. Tenants, by contrast, are charged for ACCEPTED tokens only
        (inside _verify_row)."""
        self._settle(early=True)        # drafting reads the tokens
        drafts = {}
        if self.chunked_prefill:
            budget = self._step_budget()
            for slot, row in enumerate(self._rows):
                if row is not None and "pf_seq" not in row:
                    d = self._draft_for(slot, row)
                    drafts[slot] = d
                    budget.take(d.size + 1, force=True)
            self._run_prefill_chunks(budget)
            for slot, row in enumerate(self._rows):
                if row is not None and "pf_seq" not in row \
                        and slot not in drafts:
                    d = self._draft_for(slot, row)
                    drafts[slot] = d
                    budget.take(d.size + 1, force=True)
            self._h_budget.observe(budget.used)
        self._g_occupancy.set(sum(r is not None for r in self._rows))
        alive = 0
        for slot in range(self.capacity):
            row = self._rows[slot]
            if row is None:
                continue
            if "pf_seq" in row:
                alive += 1          # mid-prefill: alive, not decoding
                continue
            d = drafts.get(slot)
            if d is None:
                # drafted lazily: either spec without chunked prefill,
                # or the row's draft map entry predates a preemption
                d = self._draft_for(slot, row)
            try:
                self._verify_row(slot, row, d)
            except Exception as e:  # noqa: BLE001 — fail THIS request,
                if self._rows[slot] is row:  # not the whole engine
                    self._fail_row_paged(slot, e)
                continue
            if self._rows[slot] is not None:
                alive += 1
        return alive

    def _grow_decode_row(self, slot, row, n_new) -> bool:
        """Grow ONE decode-ready row's page list to cover ``n_new`` new
        KV writes. Returns True iff the row survived and its table
        covers the writes; on failure the row was failed or losslessly
        self-preempted (the caller must NOT launch for it). Growth may
        preempt OTHER rows (``exclude=slot`` protects this one), with
        the anti-livelock rule that a decode-complete row outranks
        equal-or-lower-priority rows still MID-prefill — they lose the
        least work and resume losslessly."""
        bs = self.block_size
        req = row["req"]
        lens0 = int(self._lens[slot])
        target = lens0 + n_new
        if target > self.s_max:
            self._fail_row_paged(slot, RuntimeError(
                f"row exceeds engine s_max={self.s_max} at length "
                f"{lens0}"))
            return False
        extra = -(-target // bs) - len(row["pages"])
        if extra <= 0:
            return True
        pages = self._reclaim_allocate(extra, self._prio(req),
                                       exclude=slot, claimant=req,
                                       start_col=len(row["pages"]))
        if pages is None and self.chunked_prefill:
            my_p = self._prio(req)
            pf = [i for i, r in enumerate(self._rows)
                  if r is not None and i != slot and "pf_seq" in r
                  and self._prio(r["req"]) <= my_p]
            pf.sort(key=lambda i: -self._rows[i]["req"]._sched_seq)
            while pages is None and pf:
                v = pf.pop(0)
                evicted = int(self._rows[v]["pf_pos"])
                self._preempt_row(v)
                self._qos_charge(req, evicted)
                if self._cache is not None:
                    self._evict_cached(self._alloc.shortfall(
                        extra, len(row["pages"])))
                pages = self._alloc.allocate(extra, len(row["pages"]))
        if pages is None:
            others = any(r is not None and i != slot
                         for i, r in enumerate(self._rows))
            if others and self._cache is not None:
                # lossless self-preemption (mirrors the plain path)
                self._preempt_row(slot)
                return False
            self._fail_row_paged(slot, RuntimeError(
                f"paged KV pool exhausted: needed {extra} more "
                f"pages, {self._alloc.num_free} free "
                f"(n_blocks={self.n_blocks}, bs={bs})"))
            return False
        start = len(row["pages"])
        row["pages"] = row["pages"] + pages
        self._tables[slot, start:start + extra] = pages
        return True

    def _verify_row(self, slot, row, draft):
        """Grow, verify, and accept for ONE row (one device step).

        The verify window is ``[pending_tok, d1..dk]`` right-aligned in
        a bucketed ``sc`` window at ``prefix_len = lens``; the program
        returns the greedy argmax at every position. Acceptance walks
        the chain: position i's argmax is the TRUE next token iff every
        earlier draft matched, so the emitted run is exactly what k+1
        plain decode steps would have produced. State update preserves
        the resident invariant (resident == prompt + toks[:-1], length
        == lens): toks grows by the accepted run, lens by its length,
        and the new pending input is the run's last token.

        Preempt-mid-verify safety: page growth may preempt OTHER rows
        (``exclude=slot`` protects this one), and a row preempted
        BETWEEN drafting and verifying is skipped by the caller's
        ``self._rows[slot]`` re-check — it re-queues with its full
        emitted history and resumes losslessly."""
        import numpy as _np
        with _phase(self.profile, "prepare"):
            req = row["req"]
            k = int(draft.size)
            lens0 = int(self._lens[slot])
            if not self._grow_decode_row(slot, row, k + 1):
                return
            st, embed, fnorm, lm = self._weights()
            self._drain_scale_resets()
            tail = _np.empty((k + 1,), _np.int32)
            tail[0] = row["toks"][-1]   # the pending next input
            tail[1:] = draft
            sc = self._bucket_window(k + 1)
            ids = _np.full((1, sc), self.pad_id, _np.int32)
            ids[0, sc - (k + 1):] = tail
            pad = sc - (k + 1)
        t0 = _now()
        with RecordEvent("engine.spec_verify", "engine",
                         worker=self.worker_id):
            with _phase(self.profile, "launch"):
                preds, *pool = self._verify_prefill_for(sc)(
                    st, embed, fnorm, lm, self._scales, ids,
                    _np.array([pad], _np.int32),
                    _np.array([lens0], _np.int32),
                    self._tables[slot].copy(), *self._pool())
                self._set_pool(pool)
            with _phase(self.profile, "host_sync"):
                # [k+1] greedy chain
                preds = _np.asarray(preds)[0, pad:]
        with _phase(self.profile, "account"):
            wall = _now() - t0
            self.device_steps += 1
            self._c_steps.inc(1)
            self._c_device_calls.inc()
            self._h_chunk.observe(wall)
        with _phase(self.profile, "publish"):
            out = [int(preds[0])]
            for i in range(k):
                if int(draft[i]) != out[i]:
                    break
                out.append(int(preds[i + 1]))
            m_len = len(out)
            self._c_spec_proposed.inc(k)
            self._c_spec_accepted.inc(m_len - 1)
            self._h_spec_accept.observe(m_len)
            _tmark(req, "spec_verify", worker=self.worker_id)
            # (a speculative engine never runs the chunk program: the
            # device's token vector is left as it is)
            row["toks"].extend(out)
            # the draft clamp guarantees len(toks) never passes
            # max_new, so every accepted token is useful — the tenant
            # pays for exactly what it got, never for rejected
            # speculation
            _tmark(req, "decode_chunk", worker=self.worker_id,
                   n_tokens=m_len)
            self._qos_charge(req, m_len)
            if len(row["toks"]) >= req.max_new:
                req.result = _np.concatenate(
                    [row["prompt"],
                     _np.asarray(row["toks"][:req.max_new], _np.int32)])
                self._retire_paged(slot)  # pages free for next admit
                req.event.set()
                if self.qos is not None:
                    from .qos import tenant_of
                    self.qos.note_served(tenant_of(req), req.max_new)
            else:
                self._lens[slot] = lens0 + m_len

    # -- single-launch mixed step (ISSUE 10 tentpole) -----------------------
    def _mixed_windows(self):
        """The mixed step's plan: draft, fund prefill chunks from the
        step budget, grow the decode lanes, and give the ragged window
        batch as ``(slot, row, kind, tail, kv_len, table)`` a lane."""
        import numpy as _np

        def _draft(slot, row):
            return self._draft_for(slot, row) if self.spec_decode \
                else _np.zeros((0,), _np.int32)

        # plan: decode lanes force-charge their verify tokens (the
        # budget pays for PROPOSED work), prefill chunks are funded
        # from the remainder — same accounting as the per-row paths
        drafts = {}
        chunk_plan = []
        if self.chunked_prefill:
            budget = self._step_budget()
            for slot, row in enumerate(self._rows):
                if row is not None and "pf_seq" not in row:
                    d = _draft(slot, row)
                    drafts[slot] = d
                    budget.take(d.size + 1, force=True)
            slots = {}
            cands = []
            for slot, row in enumerate(self._rows):
                if row is None or "pf_seq" not in row:
                    continue
                take = min(self.prefill_chunk,
                           row["pf_seq"].size - row["pf_pos"])
                cands.append((row["req"], take))
                slots[id(row["req"])] = slot
            for req, take in self._sched.plan_prefill(budget, cands):
                chunk_plan.append((slots[id(req)], take))
            self._h_budget.observe(budget.used)
        for slot, row in enumerate(self._rows):
            if row is not None and "pf_seq" not in row \
                    and slot not in drafts:
                drafts[slot] = _draft(slot, row)
        # grow decode lanes to cover this step's writes (may preempt
        # other rows — the window build below re-checks survivors)
        for slot in sorted(drafts):
            row = self._rows[slot]
            if row is None or "pf_seq" in row:
                continue
            self._grow_decode_row(slot, row,
                                  int(drafts[slot].size) + 1)
        # build the ragged window batch: LEFT-aligned tails, kv_lens
        # INCLUDING this launch's tokens (scatter-then-attend), chunk
        # lanes through their PRIVATE tables, idle lanes q_len=0
        windows = []
        for slot, take in chunk_plan:
            row = self._rows[slot]
            if row is None or "pf_seq" not in row:
                continue        # preempted by a decode lane's growth
            pos0 = int(row["pf_pos"])
            tail = _np.asarray(row["pf_seq"][pos0:pos0 + take],
                               _np.int32)
            windows.append((slot, row, "chunk", tail,
                            pos0 + tail.size, row["pf_table"]))
        for slot in sorted(drafts):
            row = self._rows[slot]
            if row is None or "pf_seq" in row:
                continue        # preempted/failed during growth
            d = drafts[slot]
            tail = _np.empty((int(d.size) + 1,), _np.int32)
            tail[0] = row["toks"][-1]   # the pending next input
            tail[1:] = d
            windows.append((slot, row, "decode", tail,
                            int(self._lens[slot]) + tail.size,
                            self._tables[slot]))
        return windows

    def _decode_once_mixed(self):
        """ONE device launch per engine step: every decode-ready row's
        verify window (its pending token + k drafts; k=0 without spec
        decode) and every budget-funded prefill chunk ride a single
        ``mixed_paged_attention`` program with per-row ``q_lens`` —
        the O(rows)→O(1) launch collapse the ragged kernel was built
        for (the bench counts device calls to prove it). Token outputs
        are bit-identical to the per-row paths: every emitted token is
        the program's argmax at its position, and acceptance walks the
        same greedy chain ``_verify_row`` does. Schedule differs (a row
        finishing its last chunk decodes from the NEXT step, and plain
        decode lanes advance one token per launch instead of a chunk)
        but per-request greedy sequences cannot."""
        import numpy as _np
        self._settle(early=True)        # windows are built from tokens
        with _phase(self.profile, "prepare"):
            windows = self._mixed_windows()
            n_busy = sum(r is not None for r in self._rows)
            self._g_occupancy.set(n_busy)
            if not windows:
                return n_busy
            B = self.capacity
            T = self._bucket_window(max(t[3].size for t in windows))
            ids = _np.full((B, T), self.pad_id, _np.int32)
            q_lens = _np.zeros((B,), _np.int32)
            kv_lens = _np.zeros((B,), _np.int32)
            tabs = _np.zeros((B, self._max_blocks), _np.int32)
            for slot, row, kind, tail, kvl, table in windows:
                ids[slot, :tail.size] = tail
                q_lens[slot] = tail.size
                kv_lens[slot] = kvl
                tabs[slot] = table
            st, embed, fnorm, lm = self._weights()
            self._drain_scale_resets()
        t0 = _now()
        with RecordEvent("engine.mixed_step", "engine",
                         worker=self.worker_id):
            with _phase(self.profile, "launch"):
                preds, *pool = self._mixed(
                    st, embed, fnorm, lm, self._scales, ids, q_lens,
                    kv_lens, tabs, *self._pool())
                self._set_pool(pool)
            with _phase(self.profile, "host_sync"):
                # [B, T] argmax per position
                preds = _np.asarray(preds)
        with _phase(self.profile, "account"):
            wall = _now() - t0
            self.device_steps += 1
            self._c_steps.inc(1)
            self._c_device_calls.inc()
            self._h_chunk.observe(wall)
            log_event("engine_mixed_step", rows=len(windows),
                      window=T, wall_s=round(wall, 4),
                      blocks_used=self._alloc.num_used,
                      blocks_free=self._alloc.num_free)
        with _phase(self.profile, "publish"):
            for slot, row, kind, tail, kvl, table in windows:
                if self._rows[slot] is not row:
                    continue
                req = row["req"]
                if kind == "chunk":
                    take = tail.size
                    row["pf_pos"] = int(row["pf_pos"]) + take
                    self._c_prefill_chunks.inc()
                    _tmark(req, "prefill_chunk",
                           worker=self.worker_id)
                    self._qos_charge(req, take)
                    if row["pf_pos"] >= row["pf_seq"].size:
                        # last chunk: its last-real-position argmax IS
                        # the first token (mirrors _prefill_chunk_row)
                        resume = row.pop("pf_resume")
                        toks = list(resume) if resume \
                            else [int(preds[slot, take - 1])]
                        self._tables[slot] = row.pop("pf_table")
                        self._lens[slot] = row["pf_seq"].size
                        self._hand_on_known(slot, toks[-1])
                        row["toks"] = toks
                        del row["pf_seq"], row["pf_pos"]
                        self.prefills += 1
                        self._c_prefills.inc()
                        self._observe_first_token(req)
                    continue
                # decode/verify lane: greedy accept chain off the
                # window
                k = tail.size - 1
                out = [int(preds[slot, 0])]
                for i in range(k):
                    if int(tail[i + 1]) != out[i]:
                        break
                    out.append(int(preds[slot, i + 1]))
                m_len = len(out)
                if self.spec_decode:
                    self._c_spec_proposed.inc(k)
                    self._c_spec_accepted.inc(m_len - 1)
                    self._h_spec_accept.observe(m_len)
                    _tmark(req, "spec_verify", worker=self.worker_id)
                row["toks"].extend(out)
                self._hand_on_known(slot, out[-1])
                _tmark(req, "decode_chunk", worker=self.worker_id,
                       n_tokens=m_len)
                self._qos_charge(req, m_len)
                if len(row["toks"]) >= req.max_new:
                    req.result = _np.concatenate(
                        [row["prompt"],
                         _np.asarray(row["toks"][:req.max_new],
                                     _np.int32)])
                    self._retire_paged(slot)
                    req.event.set()
                    if self.qos is not None:
                        from .qos import tenant_of
                        self.qos.note_served(tenant_of(req),
                                             req.max_new)
                else:
                    self._lens[slot] = kvl - tail.size + m_len
        return sum(r is not None for r in self._rows)

class GenerationPredictor:
    """Causal-LM predictor: wraps a model with .generate() (llama/gpt
    family) for serving. ``bf16=True`` casts weights to bf16 storage
    (half the HBM, faster decode)."""

    def __init__(self, model, bf16=False, pad_id=0, int8=False):
        """``int8=True`` (VERDICT r3 #4c): weight-only int8 PTQ — the
        matmul weights live in HBM as per-channel int8 and dequantize
        inside the compiled program (models.llama.quantize_weights_int8).
        Composes with ``bf16`` (int8 weights, bf16 activations). The
        model becomes serving-only (its float weights are gone)."""
        self.model = model
        self.pad_id = int(pad_id)
        if bf16:
            import jax.numpy as jnp
            for p in model.parameters():
                if p._value.dtype == jnp.float32:
                    p._in_place_update(p._value.astype(jnp.bfloat16))
            if hasattr(model, "config"):
                model.config.dtype = "bfloat16"
        if int8:
            from ..distributed.fleet.mp_layers import current_mesh
            from ..models.llama import _pp_degree, quantize_weights_int8
            if _pp_degree(current_mesh()) > 1:
                # fail at construction, not after the float weights are
                # destroyed: pp>1 forces the re-encode generate path,
                # which has no dequantize step (ADVICE r4 #1)
                raise RuntimeError(
                    "int8 weight-only serving requires a pp=1 mesh "
                    "(the KV-cache generate path)")
            quantize_weights_int8(model)
        model.eval()

    def supports_mask(self) -> bool:
        """attention_mask support: llama rides the KV-cache path on
        pp=1 and the pipeline-prefill re-encode path on pp>1; GPT rides
        the re-encode path with pad-relative position-table lookups
        (r5). Only manual sequence parallelism (sep>1) and model
        families whose generate lacks an attention_mask parameter still
        opt out."""
        try:
            import inspect
            from ..distributed.fleet.mp_layers import current_mesh
            from ..distributed.sep import _axis_size
            if "attention_mask" not in inspect.signature(
                    self.model.generate).parameters:
                return False               # family without a masked path
            return _axis_size(current_mesh(), "sep") <= 1
        except Exception as e:  # noqa: BLE001 — unknown model family
            log_kv(_log, "supports_mask_probe_failed",
                   level=logging.DEBUG, error=type(e).__name__,
                   detail=str(e))
            return False

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, seed=0, attention_mask=None):
        """input_ids: [b, s] int array (right-aligned, pad with pad_id on
        the LEFT if rows differ — decode appends on the right). Returns
        np [b, s + max_new_tokens]. ``attention_mask`` [b, s] (1 = real
        token) lets mixed-length prompts share ONE compiled program.
        Emits a ``serve_generate`` event with measured tokens/s."""
        from ..core.tensor import Tensor
        from ..utils.log import log_event
        ids = np.asarray(input_ids)
        t0 = _now()
        out = self.model.generate(Tensor(ids),
                                  max_new_tokens=max_new_tokens,
                                  temperature=temperature, top_k=top_k,
                                  seed=seed, attention_mask=attention_mask)
        arr = np.asarray(out._value)
        dt = _now() - t0
        log_event("serve_generate", batch=int(ids.shape[0]),
                  prompt_len=int(ids.shape[1]),
                  new_tokens=int(max_new_tokens),
                  wall_s=round(dt, 4),
                  tokens_per_s=round(ids.shape[0] * max_new_tokens
                                     / max(dt, 1e-9), 1))
        return arr


class _Request:
    def __init__(self, ids, max_new, priority=0, tenant=None):
        self.ids = np.asarray(ids)
        # validate at submit, not deep in prefill: an empty prompt has
        # nothing to prefill and a non-positive budget never emits
        if self.ids.size == 0:
            raise ValueError("input_ids is empty — nothing to prefill")
        if max_new is None or int(max_new) <= 0:
            raise ValueError(
                f"max_new_tokens must be positive, got {max_new!r}")
        self.max_new = int(max_new)
        self.priority = int(priority)   # higher = sooner; can preempt
        #                                 strictly-lower running rows
        self.tenant = tenant            # QoS tenant key (None = default)
        self.trace = RequestTrace(tenant=tenant)  # lifecycle trace;
        #                                 TTFT/queue-wait derive from it
        self.event = threading.Event()
        self.result = None
        self.error = None
        self._sched_seq = None          # FCFS stamp (RequestScheduler)
        self._resume_toks = None        # preemption: emitted tokens to
        #                                 resume from losslessly
        self.retry_count = 0            # step_raised crash attributions
        #                                 (ISSUE 9 poison quarantine)

    def wait(self, timeout=None):
        if not self.event.wait(timeout):
            raise TimeoutError("generation request timed out")
        if self.error is not None:
            raise self.error
        return self.result


class BatchingServer:
    """Dynamic batching in front of a GenerationPredictor: submit() from
    any thread; a worker coalesces up to ``max_batch`` requests every
    ``max_wait_ms`` (or as soon as the batch fills), left-pads prompts to
    a common length, runs ONE generate, and resolves each request's
    future with its own row (padding stripped)."""

    def __init__(self, predictor: GenerationPredictor, max_batch=8,
                 max_wait_ms=10.0, max_new_tokens=32, continuous=False,
                 engine_kwargs=None, worker_id=None):
        """``continuous=True`` (VERDICT r4 #5): requests join/leave a
        carried-KV :class:`DecodeEngine` at chunk boundaries instead of
        riding whole batch-at-a-time generate calls — arrivals admit
        into freed slots mid-generation and finished rows retire early.

        ``worker_id`` is a stable fleet-assigned identity ("w0", ...)
        threaded into this server's (and its engine's) ``stats()`` so
        snapshots from different workers stay distinguishable."""
        self.predictor = predictor
        self.worker_id = worker_id
        self.max_batch = max_batch
        self.max_wait = max_wait_ms / 1000.0
        self.max_new_tokens = max_new_tokens
        from ..distributed.fleet.mp_layers import current_mesh
        # the mesh is thread-local: capture the constructor's mesh so
        # the worker thread serves under the SAME mesh the fallback
        # decision (and the user's sharding) was made with
        self._mesh = current_mesh()
        self.engine = None
        if continuous:
            from ..models.llama import _pp_degree
            if _pp_degree(self._mesh) > 1:
                # the engine needs the single-program decode path —
                # degrade to the masked batch loop, loudly. (Only this
                # known case degrades; any other engine-construction
                # failure propagates.)
                import warnings
                warnings.warn(
                    "continuous batching needs a pp=1 mesh; falling "
                    "back to masked batch-at-a-time", RuntimeWarning,
                    stacklevel=2)
            else:
                kw = dict(engine_kwargs or {})
                kw.setdefault("worker_id", worker_id)
                self.engine = DecodeEngine(
                    predictor.model, capacity=max_batch,
                    pad_id=predictor.pad_id, **kw)
        # share the engine's registry so server + engine metrics land in
        # one snapshot; batch-at-a-time mode gets its own
        self.metrics = self.engine.metrics if self.engine is not None \
            else MetricsRegistry()
        self._c_submitted = self.metrics.counter(
            "server_submitted_total", "requests accepted by submit()")
        self._q: queue.Queue[_Request] = queue.Queue()
        self._pending: list[_Request] = []
        self._stop = threading.Event()
        self._closed = False
        self._worker = threading.Thread(
            target=self._loop_continuous if self.engine is not None
            else self._loop, daemon=True)
        self._worker.start()

    def submit(self, input_ids, max_new_tokens=None, priority=0,
               tenant=None) -> _Request:
        """``priority`` (continuous mode): higher-priority requests
        admit first and may preempt strictly-lower running rows when
        the KV pool runs dry. ``tenant`` tags the request (and its
        trace) for multi-tenant QoS accounting. Raises ``ValueError``
        on an empty prompt or non-positive ``max_new_tokens`` — an
        explicit 0 is an error, not a fall-through to the default."""
        if self._closed:
            raise RuntimeError(
                "submit() on a closed BatchingServer: the worker is "
                "gone, the request would never be served")
        if max_new_tokens is None:
            max_new_tokens = self.max_new_tokens
        req = _Request(input_ids, max_new_tokens, priority=priority,
                       tenant=tenant)
        self._c_submitted.inc()
        self._q.put(req)
        return req

    def stats(self) -> dict:
        """Server observability: a thin view over the shared metrics
        registry plus live queue depths. ``metrics.snapshot()`` has the
        full registry (engine histograms included in continuous mode)."""
        s = {"worker_id": self.worker_id,
             "submitted": int(self._c_submitted.value),
             "queue_depth": self._q.qsize(),
             "pending": len(self._pending)}
        if self.engine is not None:
            s["engine"] = self.engine.stats()
        return s

    def close(self):
        """Idempotent: the first call stops the worker and fails every
        unserved request; later calls are no-ops."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # generous join: the first compile of a chunk can take tens of
        # seconds — touching engine state while the worker is still
        # running would race it
        self._worker.join(timeout=120)

        # fail queued-but-unserved requests fast instead of letting their
        # wait() run into its full timeout
        def _fail(req):
            req.error = RuntimeError("BatchingServer closed before the "
                                     "request was served")
            req.event.set()

        while True:
            try:
                _fail(self._q.get_nowait())
            except queue.Empty:
                break
        if self._worker.is_alive():
            return     # wedged worker still owns _pending/engine state
        for req in self._pending:
            _fail(req)
        self._pending.clear()
        if self.engine is not None:
            for slot, row in enumerate(self.engine._rows):
                if row is not None:
                    _fail(row["req"])
                    self.engine._rows[slot] = None
            for req in self.engine.drain_pending():
                _fail(req)

    # -- worker -------------------------------------------------------------
    def _take_batch(self):
        try:
            first = self._q.get(timeout=0.1)
        except queue.Empty:
            return []
        batch = [first]
        deadline = time.monotonic() + self.max_wait
        while len(batch) < self.max_batch:
            remain = deadline - time.monotonic()
            if remain <= 0:
                break
            try:
                batch.append(self._q.get(timeout=remain))
            except queue.Empty:
                break
        return batch

    def _loop(self):
        from ..distributed.fleet.mp_layers import sharding_ctx
        with sharding_ctx(self._mesh):
            self._loop_body()

    def _loop_body(self):
        while not self._stop.is_set():
            batch = self._take_batch()
            if not batch:
                continue
            try:
                self._run_batch(batch)
            except Exception as e:  # noqa: BLE001 — resolve futures
                for r in batch:
                    r.error = e
                    r.event.set()

    def _loop_continuous(self):
        from ..distributed.fleet.mp_layers import sharding_ctx
        with sharding_ctx(self._mesh):
            self._loop_continuous_body()

    def _loop_continuous_body(self):
        """Continuous batching: one iteration = drain arrivals, admit
        into free slots, ONE bounded decode chunk. Retire/admit happen
        every chunk boundary, never at generation granularity."""
        eng = self.engine
        while not self._stop.is_set():
            with _phase(eng.profile, "poll"):
                # a live row or a pending request: take what has come
                # and go on (what comes during the chunk is found at the
                # next iteration); only the idle engine waits
                try:
                    if not self._pending and eng.idle():
                        self._pending.append(self._q.get(timeout=0.05))
                    while True:
                        self._pending.append(self._q.get_nowait())
                except queue.Empty:
                    pass
            if not self._pending and eng.idle():
                continue
            try:
                eng.admit(self._pending)
                eng.decode_once()
            except Exception as e:  # noqa: BLE001 — resolve futures
                eng._unread.clear()
                for slot, row in enumerate(eng._rows):
                    if row is not None:
                        row["req"].error = e
                        row["req"].event.set()
                        eng._rows[slot] = None

    @staticmethod
    def _bucket_len(n: int) -> int:
        """Pad the prompt length up to a coarse bucket so mixed traffic
        reuses a few compiled programs instead of one per exact length
        (the mask makes the extra pads free)."""
        b = 16
        while b < n:
            b *= 2
        return b

    def _run_batch(self, batch):
        if not self.predictor.supports_mask():
            return self._run_batch_grouped(batch)
        # ONE program for the whole tick (VERDICT r3 #4a): left-pad every
        # prompt to a common bucketed length and pass the attention mask;
        # positions/attention stay correct for every row, so mixed-length
        # traffic no longer degenerates into per-length singleton batches
        max_new = max(r.max_new for r in batch)
        lens = [r.ids.reshape(-1).size for r in batch]
        s0 = self._bucket_len(max(lens))
        pad_id = self.predictor.pad_id
        rows = np.full((len(batch), s0), pad_id, np.int32)
        mask = np.zeros((len(batch), s0), np.int32)
        for i, (r, n) in enumerate(zip(batch, lens)):
            rows[i, s0 - n:] = r.ids.reshape(-1)
            mask[i, s0 - n:] = 1
        out = self.predictor.generate(rows, max_new_tokens=max_new,
                                      temperature=0.0,
                                      attention_mask=mask)
        for i, (r, n) in enumerate(zip(batch, lens)):
            # strip this row's left padding, trim to ITS asked length
            r.result = out[i, s0 - n:s0 + r.max_new]
            r.event.set()

    def _run_batch_grouped(self, batch):
        """pp>1 fallback: equal-length requests share a generate call,
        lengths run as separate sub-batches (the pre-mask behavior)."""
        by_len: dict[int, list[_Request]] = {}
        for r in batch:
            by_len.setdefault(r.ids.reshape(-1).size, []).append(r)
        for _, group in sorted(by_len.items()):
            max_new = max(r.max_new for r in group)
            rows = np.stack([r.ids.reshape(-1) for r in group])
            out = self.predictor.generate(rows, max_new_tokens=max_new,
                                          temperature=0.0)
            for i, r in enumerate(group):
                r.result = out[i, :rows.shape[1] + r.max_new]
                r.event.set()

"""The serve runner: one model behind ``BatchingServer(continuous=True)``,
the door a client uses, offered an open loop of sessions from the
traffic file at its fixed rate.

What the run is (configuration, engine sizes, lengths, rate, warm-up
shapes, the limits of ``correct``) is data: the configuration's file
and the mix's file. What it measures is read from the program's own
``RequestTrace`` marks (``perf_counter``, the clock this file schedules
on) and, in a traced run, from the per-layer readers.
"""

from __future__ import annotations

import gc
import heapq
import time

import numpy as np

from ..lib import device as dev
from ..lib import manifest as mf
from ..lib import result, stats, traffic


# -- the engine's sizes ------------------------------------------------------
def engine_kwargs(cfg, mix, trace, builder):
    """Engine options: the configuration's, then the mix's (its slots and
    ``s_max``). ``kv_pool_bytes`` becomes ``n_blocks`` by what the
    configuration's builder says one block of cache takes."""
    kw = {**cfg.get("program", {}).get("engine", {}), **mix.get("engine", {})}
    capacity = kw.pop("capacity")
    pool = kw.pop("kv_pool_bytes", None)
    if pool is not None:
        kw["n_blocks"] = int(pool) // builder.kv_bytes_per_block(
            cfg, kw.get("block_size", 16))
    if trace:
        kw["profile"] = True
    return capacity, kw


# -- one request as the harness sees it --------------------------------------
class Sent:
    __slots__ = ("turn", "due", "sent", "handle", "n_prompt", "max_new")

    def __init__(self, turn_index, turn, due):
        self.turn, self.due = turn_index, due
        self.n_prompt, self.max_new = int(turn.prompt.size), turn.max_new
        self.sent = self.handle = None

    def first(self, state):
        return self.handle.trace.first(state)

    @property
    def done(self):
        return self.handle.event.is_set()

    @property
    def ok(self):
        return self.done and self.handle.error is None


def offer_load(server, sessions, t0, seconds, poll_s=0.002):
    """Submit every turn when it is due, until the window closes. A
    session's next turn is due ``think_s`` after its last answer was
    retired. Returns the requests sent, in order."""
    due = [(t0 + s.due_s, s.index, 0) for s in sessions]
    heapq.heapify(due)
    waiting = {}                       # session index -> Sent in flight
    sent = []
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            return sent
        while due and due[0][0] <= now:
            t_due, si, k = heapq.heappop(due)
            s = sessions[si]
            req = Sent(k, s.turns[k], t_due)
            req.sent = time.perf_counter()
            req.handle = server.submit(s.turns[k].prompt,
                                       max_new_tokens=s.turns[k].max_new)
            sent.append(req)
            if k + 1 < len(s.turns):
                waiting[si] = req
        for si, req in list(waiting.items()):
            if req.done:
                del waiting[si]
                if req.ok:
                    t_ret = req.first("retired")
                    heapq.heappush(due, (t_ret + sessions[si].think_s, si,
                                         req.turn + 1))
        nxt = min(due[0][0] if due else t_end, t_end)
        if waiting:
            nxt = min(nxt, now + poll_s)
        time.sleep(max(0.0, nxt - time.perf_counter()))


def warm_up(server, mix, vocab, capacity):
    """Drive every program the mix's traffic will use through the door
    once: the cold prefill, the decode chunk, and the prefix-tail
    program of each bucket the mix lists (with a copied page)."""
    rng = np.random.default_rng(12345)
    base = traffic.warmup_prompt(rng, vocab, 40, 0)
    server.submit(base, max_new_tokens=10).wait(timeout=1800)
    for bucket in mix.get("warm", {}).get("prefix_buckets", []):
        # a tail that lands in this bucket: 12 short of a power of two,
        # or just past the last power of two for the capped top bucket
        n = bucket - 12 if bucket & (bucket - 1) == 0 \
            else 2 ** (bucket.bit_length() - 1) + 52
        tail = rng.integers(1, vocab, n, dtype=np.int32)
        server.submit(np.concatenate([base, tail]),
                      max_new_tokens=2).wait(timeout=1800)
    # every slot busy at once, so that the first full batch of the window
    # is not the first the allocator and the host loop have seen
    many = [server.submit(traffic.warmup_prompt(rng, vocab, 24, 1 + i),
                          max_new_tokens=10) for i in range(capacity)]
    for h in many:
        h.wait(timeout=1800)


class StepTimer:
    """Wall time of each ``decode_once``, from a wrapper round the bound
    method (traced runs only)."""

    def __init__(self, engine):
        self.walls = []
        inner = engine.decode_once

        def timed():
            t0 = time.perf_counter()
            try:
                return inner()
            finally:
                self.walls.append((t0, time.perf_counter() - t0))

        engine.decode_once = timed


# -- the run -----------------------------------------------------------------
def run(ctx):
    """``ctx``: cell, cfg, mix, manifest, seed, seconds, trace, devices,
    t_process, t_imports, t_chip, control. Returns the result object."""
    import jax
    from paddle_tpu.inference.serving import (BatchingServer,
                                              GenerationPredictor)
    cfg, mix, seconds = ctx["cfg"], ctx["mix"], float(ctx["seconds"])
    devices = ctx["devices"]
    builder, reference = mf.serve_modules(cfg)
    watch = dev.CompileWatch()
    # where set-up's seconds go: a mark at the end of each phase
    marks = [("imports", ctx["t_imports"]), ("chip", ctx["t_chip"]),
             ("serving", time.perf_counter())]
    model = builder.build_model(cfg, ctx["seed"])
    marks.append(("weights", time.perf_counter()))
    capacity, kw = engine_kwargs(cfg, mix, ctx["trace"], builder)
    server = BatchingServer(GenerationPredictor(model), max_batch=capacity,
                            continuous=True, engine_kwargs=kw)
    engine = server.engine
    marks.append(("engine", time.perf_counter()))
    vocab = cfg["vocab_size"]
    warm_up(server, mix, vocab, capacity)
    marks.append(("warm_up", time.perf_counter()))
    compile_s, lowered_setup = watch.compile_s, watch.lowered
    sessions = traffic.schedule(mix, ctx["seed"], seconds, vocab)
    timer = StepTimer(engine) if ctx["trace"] else None
    before = engine.stats()
    lowered_before = watch.lowered
    gc.collect()
    gc.freeze()

    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_process"]
    marks.append(("schedule", t0))
    tracer = result.start_trace(ctx, t0)
    sent = offer_load(server, sessions, t0, seconds)
    t_close = time.perf_counter()
    completed = [r for r in sent if r.ok and r.first("retired") <= t_close]
    # a short grace so that requests due late in the window can still show
    # their first token; nothing that finishes in it counts as completed
    grace_end = t_close + float(mix.get("grace_s", 3.0))
    while time.perf_counter() < grace_end and any(
            not r.done and r.first("first_token") is None for r in sent):
        time.sleep(0.01)
    if tracer is not None:
        tracer.join()
    after = engine.stats()
    lowered_in_window = watch.lowered - lowered_before
    # what failed by itself, before close() fails whatever is in flight
    failed = [r for r in sent if r.done and r.handle.error is not None]
    finished = [r for r in sent if r.ok]

    def backlog(t):
        return sum(1 for r in sent if r.sent <= t
                   and not (r.ok and r.first("retired") <= t))
    backlogs = (backlog(t0 + (t_close - t0) / 2), backlog(t_close))
    server.close()
    peak = dev.memory_peak_bytes(devices)

    # -- end-to-end metrics ---------------------------------------------------
    window_s = t_close - t0
    ttft = [r.first("first_token") - r.due for r in sent
            if r.first("first_token") is not None]
    no_first = len(sent) - len(ttft)
    ttft_all = stats.with_failures(ttft, no_first, window_s)
    tpot = [(r.first("retired") - r.first("first_token")) / (r.max_new - 1)
            for r in finished if r.max_new > 1]
    out_tokens = sum(r.max_new for r in completed)
    # every output token stamped inside the window: first tokens and the
    # tokens of each decode chunk (a rate over all the work of the window)
    emitted = sum(1 for r in sent if (r.first("first_token") or t_close) < t_close) \
        + sum(n for r in sent for t, n in decode_marks(r) if t < t_close)
    # with no request finished, a token took the window
    latencies = {"ttft": ttft_all, "tpot": tpot or [window_s]}
    e2e = {"setup_s": (setup_s, "s"),
           "serve_tok_s": (emitted / window_s, "tokens/s")}
    for metric in ctx["manifest"]["end_to_end"]:
        if metric["name"] not in e2e and mf.metric_reports_in(
                metric, ctx["cell"]["name"], ctx["manifest"]):
            e2e[metric["name"]] = (latency_metric(metric["name"], latencies), "ms")
    print(f"tokens: completed_requests {out_tokens} emitted_in_window {emitted} "
          f"emitted_tok_s {emitted / window_s:.3f}")
    print(f"samples: sent {len(sent)} first_token {len(ttft)} "
          f"finished {len(finished)} completed_in_window {len(completed)} "
          f"failed {len(failed)} no_first_token {no_first} "
          f"beyond_p95 ttft {stats.beyond(len(ttft_all), 95)} "
          f"tpot {stats.beyond(len(tpot), 95)}")
    print(f"backlog: mid_window {backlogs[0]} end_of_window {backlogs[1]}")
    print(f"window: window_s {window_s:.3f} setup_s {setup_s:.3f}")
    # the whole ladder, for whoever has to choose a steadier percentile
    for name, vals in latencies.items():
        print(f"ladder: {name}_mean_ms {1e3 * sum(vals) / len(vals):.3f} "
              + " ".join(f"{name}_p{q}_ms {1e3 * stats.percentile(vals, q):.3f}"
                         for q in (50, 75, 80, 85, 90, 95, 99)))
    prompt_admitted = sum(r.n_prompt for r in sent
                          if (r.first("admitted") or t_close + 1) <= t_close)
    starts = [ctx["t_process"]] + [t for _, t in marks]
    print("setup: " + " ".join(f"{name} {t - t_prev:.3f}"
                               for (name, t), t_prev in zip(marks, starts))
          + f" compile_or_cache_read_s {compile_s:.3f} "
          f"programs_lowered {lowered_setup}")
    print(f"engine: admitted {after['admitted'] - before['admitted']} "
          f"preempted {after['preempted'] - before['preempted']} "
          f"prefix_hit_tokens "
          f"{after['prefix_hit_tokens'] - before['prefix_hit_tokens']} "
          f"prompt_tokens_admitted {prompt_admitted} "
          f"pool {after.get('pool')} n_blocks {engine.n_blocks} "
          f"s_max {engine.s_max} capacity {capacity}")

    # -- free the program, then the reference ---------------------------------
    records = [{"n_prompt": r.n_prompt, "due": r.due, "sent": r.sent,
                "events": list(r.handle.trace.events),
                "chunks": decode_marks(r),
                "queue_wait": r.handle.trace.queue_wait,
                "ok": r in finished} for r in sent]
    sample = pick_sample(completed, ctx["seed"],
                         int(mix.get("check", {}).get("sample", 3)))
    sequences = [(np.asarray(r.handle.result), r.n_prompt) for r in sample]
    del server, engine, model, sent, completed, finished, sample, sessions
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    t_ref = time.perf_counter()
    checks = check_served(reference, cfg, mix, ctx["seed"], sequences,
                          control=ctx["control"])
    print(f"reference: took {time.perf_counter() - t_ref:.1f} s")
    checks.append(("programs_lowered_in_window", lowered_in_window, 0))
    checks.append(("requests_failed", len(failed), 0))
    correct = result.print_checks(checks) and bool(sequences)
    return result.assemble(
        ctx, correct, len(records), len(failed) + no_first, peak, e2e,
        {"records": records, "window": (t0, t_close), "before": before,
         "after": after, "latencies": latencies, "step_walls": timer.walls if timer else None,
         "trace_span": tracer.span if tracer else None},
        checks=checks)


def latency_metric(name, latencies):
    """Which statistic of the time to the first token or of the time per
    token after it is an end-to-end metric is BENCHMARK.json's to say, by
    the metric's name (``stats.latency_statistic``), over every request
    of the window."""
    value = stats.latency_statistic(name, latencies)
    if value is None:
        raise SystemExit(f"benchmark: the serve runner has no end-to-end "
                         f"metric named {name!r}")
    return value


def decode_marks(r):
    """(time, tokens) of each ``decode_chunk`` mark of a request."""
    tr = r.handle.trace
    return [(t, tr.tokens_of(i) or 0) for i, (st, t) in enumerate(tr.events)
            if st == "decode_chunk"]


def pick_sample(completed, seed, n):
    """The longest finished request and ``n - 1`` more, drawn from the
    seed."""
    if not completed:
        return []
    order = sorted(completed, key=lambda r: (r.n_prompt + r.max_new, r.due))
    longest = order[-1]
    rest = order[:-1]
    rng = np.random.default_rng([int(seed), 0x5A])
    idx = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in idx]


def check_served(reference, cfg, mix, seed, sequences, control=False):
    """The numbers ``correct`` compares, each with its limit: over the
    sampled requests, the widest and the mean gap by which a served
    token's float32 logit in the configuration's ``reference`` lies
    below that reference's best."""
    limits = mix.get("check", {}).get("limits", {})
    served, ctrl = [], []
    for seq, n_prompt in sequences:
        gaps = reference.served_gaps(seed, cfg, seq, n_prompt, control=control)
        served.append(gaps["served"])
        if control:
            ctrl.append(gaps["control"])
    if not served:
        return []
    served = np.concatenate(served)
    print(f"reference: {len(sequences)} requests, {served.size} served tokens "
          f"compared, longest sequence {max(s.size for s, _ in sequences)}")
    # how the gaps lie, for whoever sets the next limit (no limit yet)
    print("gaps: " + " ".join(f"share_over_{t:g} {float((served > t).mean()):.4f}"
                              for t in (0.0, 0.05, 0.1)))
    out = [("gap_max", float(served.max()), limits.get("gap_max", 0.0)),
           ("gap_mean", float(served.mean()), limits.get("gap_mean", 0.0))]
    if control:
        ctrl = np.concatenate(ctrl)
        out += [("control.gap_max", float(ctrl.max()), limits.get("gap_max", 0.0)),
                ("control.gap_mean", float(ctrl.mean()),
                 limits.get("gap_mean", 0.0))]
    return out

"""The one general traffic generator. A mix is a data file of parameters
(benchmark/traffic/<mix>.json); this module turns it and a seed into a
schedule of sessions, and nothing in it knows a mix by name.

Arrival processes (poisson, bursty = Markov-modulated Poisson) and the
bounded-Pareto length follow paddle_tpu/inference/traffic.py,
which runs in virtual time and has no sessions; copied and extended here
with sessions, shared documents, lognormal lengths and real due times.

One rule orders the work. Lengths and Poisson gaps are the
distribution's quantiles at (i + 0.5) / n, permuted by the mix's
``"order_seed"`` (required; bursty gaps are drawn from it): every run of
a mix offers the same requests at the same times, whatever its ``--seed``. The run's seed draws the
token ids (and, in the runner, the weights). Two runs then differ by the
system's noise and not by the draw's; what this hides is in PERF.md
(section 2, "The schedule is fixed").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Turn:
    prompt: np.ndarray          # full prompt: document + question
    max_new: int
    fresh_len: int              # tokens not shared with the session


@dataclass
class Session:
    index: int
    due_s: float                # first turn, seconds from window start
    think_s: float              # next turn is due this long after an answer
    turns: list = field(default_factory=list)


RESERVED_LEADS = 64     # leading tokens kept for warm-up prompts


def warmup_prompt(rng, vocab_size, length, index):
    """A warm-up prompt whose leading token no scheduled prompt has."""
    out = rng.integers(1, vocab_size, int(length), dtype=np.int32)
    out[0] = vocab_size - 1 - (index % RESERVED_LEADS)
    return out


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lengths(spec, n, rng):
    """``n`` whole lengths from a length spec: {"dist": lognormal |
    uniform | pareto, ..., "min", "max"}: the distribution's
    quantiles, in the order ``rng`` gives."""
    if n == 0:
        return np.zeros(0, np.int64)
    u = _quantiles(n)
    dist = spec["dist"]
    if dist == "uniform":
        raw = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in u])
        raw = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "pareto":
        raw = spec["min"] * (1.0 - u) ** (-1.0 / spec["alpha"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    out = np.clip(np.floor(raw), spec["min"], spec["max"]).astype(np.int64)
    return out[rng.permutation(n)]


def arrival_times(spec, horizon_s, rng):
    """Session start times in [0, horizon): {"process": poisson |
    bursty, "rate_per_s", ...}."""
    rate = float(spec["rate_per_s"])
    process = spec["process"]
    if process == "poisson":
        n = max(1, round(rate * horizon_s))
        gaps = -np.log1p(-_quantiles(n)) / rate
        # the set fills the window, the last arrival half a mean gap
        # short of its end, so that all n lie inside
        gaps *= horizon_s / (gaps.sum() + 0.5 / rate)
        return [float(t) for t in np.cumsum(gaps[rng.permutation(n)])]
    if process == "bursty":
        on_rate = rate * float(spec["burst_factor"])
        off_rate = rate * float(spec.get("off_factor", 0.1))
        out, t, phase_end, on = [], 0.0, 0.0, False
        while t < horizon_s:
            if t >= phase_end:
                on = not on
                dwell = spec["on_dwell_s"] if on else spec["off_dwell_s"]
                phase_end = t + rng.exponential(float(dwell))
            t += rng.exponential(1.0 / (on_rate if on else off_rate))
            if t < horizon_s:
                out.append(t)
        return out
    raise ValueError(f"unknown arrival process {process!r}")


def schedule(mix, seed, horizon_s, vocab_size):
    """All sessions of one run, a pure function of (mix, seed, horizon,
    vocabulary). Tokens are drawn in [1, vocab): 0 is the pad id."""
    rng = np.random.default_rng([int(mix["order_seed"]), 0x7261])
    tok_rng = np.random.default_rng([int(seed), 0x746F])
    times = arrival_times(mix["arrivals"], horizon_s, rng)
    n = len(times)
    turns = int(mix.get("turns", 1))
    doc_lens = lengths(mix["document"], n, rng) \
        if mix.get("document") else np.zeros(n, np.int64)
    q_lens = lengths(mix["prompt"], n * turns, rng)
    out_lens = lengths(mix["output"], n * turns, rng)
    # Every prompt opens with a token no other session's does (and none of
    # the warm-up's, which take theirs from the top RESERVED ids): the
    # prefix cache matches partial pages, so two unrelated prompts that
    # happened to open alike would share one token, and the engine would
    # route the whole second prompt through a prefix-tail program of a
    # bucket this mix never warmed.
    if n * turns > vocab_size - 1 - RESERVED_LEADS:
        raise ValueError("more prompts than distinct leading tokens")
    leads = 1 + tok_rng.permutation(vocab_size - 1 - RESERVED_LEADS)[:n * turns]
    sessions = []
    for i, t in enumerate(times):
        doc = tok_rng.integers(1, vocab_size, int(doc_lens[i]), dtype=np.int32)
        if doc.size:
            doc[0] = leads[i * turns]
        s = Session(index=i, due_s=float(t),
                    think_s=float(mix.get("think_s", 0.0)))
        for k in range(turns):
            j = i * turns + k
            fresh = tok_rng.integers(1, vocab_size, int(q_lens[j]),
                                     dtype=np.int32)
            if not doc.size:
                fresh[0] = leads[j]
            s.turns.append(Turn(prompt=np.concatenate([doc, fresh]),
                                max_new=int(out_lens[j]),
                                fresh_len=int(q_lens[j])))
        sessions.append(s)
    return sessions


def longest_request(mix):
    """(prompt tokens, new tokens) no request of the mix exceeds."""
    doc = mix["document"]["max"] if mix.get("document") else 0
    return doc + mix["prompt"]["max"], mix["output"]["max"]

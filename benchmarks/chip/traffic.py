"""Traffic from ``--seed``: open-loop serving requests and training batches.

Every seed gets the same amount of work: the same set of inter-arrival
gaps, prompt lengths, system-prompt picks and output lengths (stratified
quantiles of the mix's distributions), dealt out in an order drawn from
the seed, with the seed's own token contents. So two seeds differ in
the order of the work, never in how much there is; the order alone
still decides whether long answers overlap and requests queue for a
slot, and each seed sees an order of its own.

Mixes (``traffic/<mix>.json``) name a ``kind`` and its parameters:

* ``sessions`` — open-loop Poisson arrivals at ``rate_per_s``; a share
  ``shared_share`` of prompts starts with one of ``system_prompts``
  system prompts of ``system_len`` tokens (picked by Zipf with exponent
  ``zipf_s``) and continues with a user turn of ``user_len`` tokens; the
  rest are unique prompts of ``unique_len`` tokens. Lengths are
  lognormal (``median``, ``sigma``) clipped to [``min``, ``max``].
  Outputs (``output_len``) are greedy, with no end-of-sequence token.
  The length and prefix generators follow ``serve/workload.py``, with
  arrivals in seconds instead of scheduler ticks.
* ``synthetic_lm`` — training batches of ``batch`` rows of ``seq``
  tokens, a copy of ``data/synthetic.batch_at`` (Zipf unigrams with
  copied motifs).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def generator(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; any
    non-negative whole number is a seed (negative ones are folded)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream]))


def lognormal_quantiles(n: int, spec: Dict) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as whole tokens."""
    if n <= 0:
        return np.zeros(0, np.int64)
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) * mean


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of ``n`` draws land on each of ``k`` ranks under Zipf(s),
    by largest remainder (sums to ``n`` exactly)."""
    p = 1.0 / np.arange(1, k + 1) ** s
    p /= p.sum()
    raw = p * n
    c = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - c), kind="stable")[: n - int(c.sum())]:
        c[i] += 1
    return c


@dataclass
class ServeRequest:
    rid: str
    due_s: float               # seconds after the window opens
    tokens: np.ndarray         # (L,) int32 prompt
    max_new_tokens: int
    prefix_id: int             # system prompt index, -1 for unique


def sessions(mix: Dict, seed: int, seconds: float,
             vocab: int) -> List[ServeRequest]:
    """The requests due in a window of ``seconds`` (all of them due
    before it closes)."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    g = generator(seed, 1)
    gaps = g.permutation(exponential_gaps(n, 1.0))
    due = np.cumsum(gaps) * (seconds * n / (n + 1) / gaps.sum())
    n_shared = int(round(mix.get("shared_share", 0.0) * n))
    shared = np.zeros(n, bool)
    shared[g.permutation(n)[:n_shared]] = True
    k = int(mix.get("system_prompts", 0))
    pid = np.full(n, -1, np.int64)
    if n_shared:
        counts = zipf_counts(n_shared, k, mix.get("zipf_s", 1.0))
        pid[shared] = g.permutation(np.repeat(np.arange(k), counts))
    body = np.zeros(n, np.int64)
    if n_shared:
        body[shared] = g.permutation(lognormal_quantiles(n_shared,
                                                         mix["user_len"]))
    if n - n_shared:
        body[~shared] = g.permutation(
            lognormal_quantiles(n - n_shared, mix["unique_len"]))
    outs = g.permutation(lognormal_quantiles(n, mix["output_len"]))
    toks = generator(seed, 2)
    systems = [toks.integers(0, vocab, mix["system_len"], dtype=np.int32)
               for _ in range(k)]
    reqs = []
    for i in range(n):
        tail = toks.integers(0, vocab, int(body[i]), dtype=np.int32)
        prompt = (np.concatenate([systems[pid[i]], tail]) if pid[i] >= 0
                  else tail)
        reqs.append(ServeRequest(f"r{i}", float(due[i]), prompt,
                                 int(outs[i]), int(pid[i])))
    return reqs


def prompt_length_range(mix: Dict) -> "tuple[int, int]":
    """Shortest prompt suffix and longest prompt the mix can produce."""
    lo, hi = [], []
    if mix.get("shared_share", 0.0) > 0:
        lo.append(mix["user_len"]["min"])
        hi.append(mix["system_len"] + mix["user_len"]["max"])
    if mix.get("shared_share", 0.0) < 1:
        lo.append(mix["unique_len"]["min"])
        hi.append(mix["unique_len"]["max"])
    return min(lo), max(hi)


def warmup_lengths(mix: Dict) -> List[int]:
    """Prompt lengths whose admission compiles every prefill shape this
    mix can reach: each power of two between its shortest suffix and its
    longest prompt, and those two ends."""
    lo, hi = prompt_length_range(mix)
    out = {lo, hi}
    p = 1
    while p < hi:
        if p > lo:
            out.add(p)
        p *= 2
    return sorted(out)


def synthetic_lm(vocab: int, batch: int, seq: int, seed: int,
                 step: int) -> Dict[str, np.ndarray]:
    """One training batch: a copy of ``data/synthetic.batch_at``'s
    Zipf-unigram stream with copied motifs, drawn per (seed, step)."""
    rng = generator(seed, 3, step)
    ranks = np.arange(1, vocab + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()
    toks = rng.choice(vocab, size=(batch, seq + 1), p=probs).astype(np.int32)
    if seq >= 64:
        w = 16
        src = rng.integers(0, seq // 2 - w, size=batch)
        dst = rng.integers(seq // 2, seq - w, size=batch)
        for i in range(batch):
            toks[i, dst[i]:dst[i] + w] = toks[i, src[i]:src[i] + w]
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

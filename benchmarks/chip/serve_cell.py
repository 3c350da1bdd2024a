"""A serving cell: open-loop requests through ``ServeEngine``.

Set-up builds the engine as the configuration's ``engine`` block fixes
it (dtypes are left to the program), with the tier-3 serving detectors
and tier-4 kernel counters attached where its ``profiler`` block asks,
and admits one warm-up prompt per prefill shape the mix can reach. The
window then submits each request when it is due (``traffic.sessions``)
and steps the engine while anything is pending; every token is stamped
when the step that produced it returns. After the window closes no new
request is due; the engine drains what is left (up to ``DRAIN_S``).

Latency counts from when a request was due: TTFT is due to first token,
inter-token gaps are between consecutive tokens of one request. The
window ends with the step that is running when ``--seconds`` pass.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmarks.chip import check, traffic, weights
from benchmarks.chip.common import (GCClock, TracedSpan, log, model_config,
                                    pct)

DRAIN_S = 60.0


class HookTimer:
    """Host seconds spent inside a detectors object's ``on_*`` hooks."""

    def __init__(self, det):
        self.seconds = 0.0
        for name in dir(det):
            if name.startswith("on_") and callable(getattr(det, name)):
                setattr(det, name, self._timed(getattr(det, name)))

    def _timed(self, fn):
        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                self.seconds += time.perf_counter() - t0
        return wrapped


@dataclass
class StepRecord:
    start: float
    end: float
    admitted: List[tuple] = field(default_factory=list)   # (start, length)
    tick_ctx: List[int] = field(default_factory=list)     # keys per live slot
    tokens: int = 0


def build_engine(cell, seed: int, params, model):
    from repro.configs.base import ProfilerConfig
    from repro.core.detectors import ServingDetectors
    from repro.serve.engine import ServeEngine
    prof = cell.config.get("profiler") or {}
    det = timer = None
    if prof.get("serving_detectors"):
        det = ServingDetectors(ProfilerConfig(enabled=True,
                                              seed=seed % (1 << 32)))
        timer = HookTimer(det)
    eng = ServeEngine(model, params, detectors=det,
                      kernel_counters=bool(prof.get("kernel_counters")),
                      **cell.config["engine"])
    return eng, timer


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        clock, since_start, fault=None) -> Dict[str, Any]:
    """One run; returns what ``run.py`` reports. ``fault(engine)`` plants
    a fault in the timed path (tests only)."""
    from repro.models.zoo import build_model
    from repro.serve.engine import Request
    mix = cell.traffic
    mcfg = model_config(cell.config)
    model = build_model(mcfg)
    V = mcfg.vocab_size
    abstract = model.abstract_params()
    params = weights.make_params(abstract, seed, mcfg.d_model)
    eng, timer = build_engine(cell, seed, params, model)
    log(f"[setup] weights and engine at {since_start()!r} s")
    if fault is not None:
        fault(eng)

    # four tokens each: ticks with a live slot, where attached detectors
    # arm their first watchpoint (its K/V read is a program of its own)
    g = traffic.generator(seed, 9)
    for n in traffic.warmup_lengths(mix):
        eng.submit(Request(rid=f"warm{n}",
                           tokens=g.integers(0, V, n, dtype=np.int32),
                           max_new_tokens=4))
        eng.run()
    reqs = traffic.sessions(mix, seed, seconds, V)
    jax.block_until_ready(eng.cache)
    log(f"[setup] {len(traffic.warmup_lengths(mix))} warm-up prompts at "
        f"{since_start()!r} s")

    stats0 = dict(eng.stats)
    hooks0 = _hook_s(timer)
    compiles0 = clock.count
    setup_s = since_start()

    due = {r.rid: r.due_s for r in reqs}
    times: Dict[str, List[float]] = {r.rid: [] for r in reqs}
    submitted: Dict[str, Any] = {}
    lateness: List[float] = []
    admitted_at: Dict[str, tuple] = {}        # rid -> (step start, wait)
    steps: List[StepRecord] = []
    live: Dict[str, Any] = {}
    traced = TracedSpan(trace_dir, seconds)
    before_trace = None                       # counters when it started
    win_end = None
    backlog = 0
    i = 0
    jax.config.update("jax_log_compiles", True)   # name any in the window
    gcs = GCClock().install()
    t0 = time.perf_counter()
    now = lambda: time.perf_counter() - t0
    while True:
        t = now()
        if win_end is None and t >= seconds:
            win_end, backlog = t, eng.pending
            jax.config.update("jax_log_compiles", False)
        if traced.poll(t, now):
            before_trace = (dict(eng.stats), _hook_s(timer), len(steps))
        while i < len(reqs) and reqs[i].due_s <= t:
            r = reqs[i]
            req = Request(rid=r.rid, tokens=r.tokens,
                          max_new_tokens=r.max_new_tokens)
            eng.submit(req)
            submitted[r.rid] = req
            live[r.rid] = req
            lateness.append(now() - r.due_s)
            i += 1
        if not eng.pending:
            if i == len(reqs):
                break
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(reqs[i].due_s - now(), 0.05)))
            continue
        if t >= seconds + DRAIN_S:
            break
        before = {rid: len(q.generated) for rid, q in live.items()}
        k = eng.step_no
        ts = now()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
        te = now()
        rec = StepRecord(ts, te)
        for rid, q in list(live.items()):
            new = len(q.generated) - before[rid]
            admitted = q.prefill_step == k
            if admitted:
                admitted_at[rid] = (ts, ts - due[rid])
                rec.admitted.append((q.reuse_len, q.tokens.size - q.reuse_len))
            if new > (1 if admitted else 0):
                rec.tick_ctx.append(q.tokens.size + len(q.generated) - 1)
            if new:
                times[rid].extend([te] * new)
                rec.tokens += new
            if q.done:
                del live[rid]
        steps.append(rec)
        if win_end is None and te >= seconds:
            win_end, backlog = te, eng.pending
            jax.config.update("jax_log_compiles", False)
    traced.close(now)
    gcs.remove()
    jax.config.update("jax_log_compiles", False)
    win_end = max(win_end or now(), seconds)

    stats = {k: v - stats0[k] for k, v in eng.stats.items()
             if isinstance(v, (int, float))}
    ttft, gaps = [], []
    failed = 0
    out_tokens = 0
    for r in reqs:
        ts_ = times[r.rid]
        want = min(r.max_new_tokens, eng.max_len - r.tokens.size)
        if len(ts_) < want:
            failed += 1
            continue
        ttft.append(ts_[0] - r.due_s)
        out_tokens += sum(1 for x in ts_ if x <= win_end)
        gaps += [b - a for a, b in zip(ts_, ts_[1:]) if b <= win_end]
    compiles = clock.count - compiles0
    late = np.asarray(lateness)
    log(f"[serve] window {win_end:.3f} s, requests due {len(reqs)}, "
        f"finished {len(reqs) - failed}, failed {failed}, steps "
        f"{len(steps)}, compiles in window {compiles}, pending at close "
        f"{backlog}, output tokens/s {out_tokens / win_end!r}")
    if ttft:
        log(f"[serve] ttft p50 {1e3 * pct(ttft, 50):.2f} ms p70 "
            f"{1e3 * pct(ttft, 70):.2f} ms p95 "
            f"{1e3 * pct(ttft, 95):.2f} ms (n={len(ttft)}); itl p50 "
            f"{1e3 * pct(gaps, 50):.2f} ms p95 {1e3 * pct(gaps, 95):.2f} ms "
            f"(n={len(gaps)}); generator late p50 "
            f"{1e3 * float(np.median(late)):.3f} ms max "
            f"{1e3 * float(late.max()):.3f} ms")
    log(f"[serve] engine counters in window: {stats}; "
        + gcs.summary(t0))
    # host-side layer counters over the window before the profiler
    # started (its start and stop pause the host), or all of it
    cut = traced.start if traced.start is not None else float("inf")
    if before_trace is not None:
        st_pre, hooks_pre, nsteps = before_trace
    else:
        st_pre, hooks_pre, nsteps = dict(eng.stats), _hook_s(timer), len(steps)
    counters = {
        "stats": stats,
        "stats_before_trace": {k: v - stats0[k] for k, v in st_pre.items()
                               if isinstance(v, (int, float))},
        "queue_wait_s": [w for a, w in admitted_at.values() if a < cut],
        "ttft_s": ttft,
        "steps_in_trace": [s for s in steps if traced.covers(s.start, s.end)],
        "trace_host_s": traced.host_s,
        "hook_s": None if timer is None else hooks_pre - hooks0,
        "steps_before_trace": nsteps,
        "steps": len(steps), "output_tokens": out_tokens,
        "window_s": win_end, "compiles_in_window": compiles,
        "pending_at_close": backlog,
    }
    result = {
        "setup_s": setup_s, "attempted": len(reqs), "failed": failed,
        "metrics": {
            "ttft_p70_ms": 1e3 * pct(ttft, 70) if ttft else None,
            "itl_p95_ms": 1e3 * pct(gaps, 95) if gaps else None,
            "output_tokens_per_s": out_tokens / win_end,
        },
        "counters": counters, "model": cell.config["model"],
        "pool_itemsize": _pool_itemsize(eng),
    }

    finished = [submitted[r.rid] for r in reqs
                if r.rid in submitted and submitted[r.rid].done]
    sample = check.serve_sample(finished, seed,
                                cell.config["check"]["sample"])
    result["check_inputs"] = [(np.asarray(q.tokens), list(q.generated))
                              for q in sample]
    del eng, params, live, submitted, finished, sample
    gc.collect()
    return result


def _hook_s(timer) -> float:
    return timer.seconds if timer is not None else 0.0


def _pool_itemsize(eng) -> int:
    main = eng.cache["main"]
    sub = next(s for s in main.values() if "k" in s)
    return int(sub["k"].dtype.itemsize)


def correctness(cell, seed: int, result) -> Dict[str, Dict[str, float]]:
    """After the program's state is freed: the plain reference over the
    sampled requests."""
    from repro.models.zoo import build_model
    model = build_model(model_config(cell.config))
    abstract = model.abstract_params()
    params = weights.make_params(abstract, seed, cell.config["model"]["d_model"])
    gap = check.serve_gap(params, cell.config["model"],
                          result["check_inputs"],
                          cell.config["engine"]["max_len"])
    lim = cell.config["check"]["max_logit_gap"]
    return {"max_logit_gap": {"value": gap, "limit": lim}}

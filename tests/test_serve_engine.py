"""Serving engine: batched-prefill equivalence (bit-identical cache,
identical greedy continuations), continuous batching against per-sequence
references, slot recycling, honest throughput accounting, and the
serve-side Tier-3 KV-cache waste detectors."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ProfilerConfig
from repro.core.detectors import ServingDetectors
from repro.models.zoo import build_model
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _model(arch="qwen3-1.7b"):
    cfg = dataclasses.replace(registry.get_config(arch).smoke(),
                              dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _reference_generate(model, params, prompt, gen, max_len):
    """Per-sequence token-by-token greedy loop (the seed serving path)."""
    cache = model.init_cache(params, 1, max_len, kv_dtype=jnp.float32)
    toks = jnp.asarray(prompt)[None, :]
    for t in range(prompt.size):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
    out = [int(jnp.argmax(lg[:, -1]))]
    cur = jnp.asarray([[out[-1]]], jnp.int32)
    for _ in range(gen - 1):
        lg, cache = model.decode_step(params, cache, cur)
        cur = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        out.append(int(cur[0, 0]))
    return out, cache


# ----------------------------------------------------------------------
# Batched prefill == token-by-token loop (the PR's regression criterion)
# ----------------------------------------------------------------------
def test_batched_prefill_bit_identical_cache_and_continuation():
    cfg, model, params = _model()
    B, P, G = 2, 12, 5
    toks = jax.random.randint(KEY, (B, P), 0, cfg.vocab_size)
    max_len = P + G + 1

    loop = model.init_cache(params, B, max_len, kv_dtype=jnp.float32)
    for t in range(P):
        lg_loop, loop = model.decode_step(params, loop, toks[:, t:t + 1])
    batched = model.init_cache(params, B, max_len, kv_dtype=jnp.float32)
    lg_pre, batched = model.prefill(params, batched, toks)

    for a, b in zip(jax.tree_util.tree_leaves(loop),
                    jax.tree_util.tree_leaves(batched)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(lg_loop[:, -1]),
                                  np.asarray(lg_pre[:, -1]))

    def continue_greedy(cache, lg):
        nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        out = [np.asarray(nxt)]
        for _ in range(G - 1):
            lg, cache = model.decode_step(params, cache, nxt)
            nxt = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
            out.append(np.asarray(nxt))
        return np.concatenate(out, 1)
    np.testing.assert_array_equal(continue_greedy(loop, lg_loop),
                                  continue_greedy(batched, lg_pre))


def test_prefill_per_row_lengths_match_per_sequence():
    """Padded variable-length prefill with per-slot write indices equals
    each sequence prefilled alone."""
    cfg, model, params = _model()
    B, Pmax, G = 2, 10, 4
    lens = np.array([10, 6])
    toks = np.asarray(jax.random.randint(KEY, (B, Pmax), 0, cfg.vocab_size))
    max_len = Pmax + G + 2

    cache = model.init_cache(params, B, max_len, kv_dtype=jnp.float32)
    cache = model.with_cache_index(cache, jnp.zeros((B,), jnp.int32))
    lg, cache = model.prefill(params, cache, jnp.asarray(toks),
                              lengths=jnp.asarray(lens))
    nxt = jnp.argmax(lg[jnp.arange(B), lens - 1], -1).astype(jnp.int32)
    got = [np.asarray(nxt)]
    cur = nxt[:, None]
    for _ in range(G - 1):
        lg, cache = model.decode_step(params, cache, cur)
        cur = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        got.append(np.asarray(cur[:, 0]))
    got = np.stack(got, 1)

    for b in range(B):
        ref, _ = _reference_generate(model, params, toks[b, :lens[b]], G,
                                     max_len)
        np.testing.assert_array_equal(got[b], np.array(ref))


# ----------------------------------------------------------------------
# Continuous batching
# ----------------------------------------------------------------------
def test_engine_continuous_batching_matches_isolated_requests():
    """More requests than slots, staggered arrivals, different prompt
    lengths and budgets: every request's greedy output must equal the
    same prompt served alone."""
    cfg, model, params = _model()
    max_len = 24
    eng = ServeEngine(model, params, num_slots=2, max_len=max_len)
    rng = np.random.RandomState(3)
    reqs = []
    for i, (plen, gen, arr) in enumerate(
            [(8, 4, 0), (5, 6, 0), (7, 3, 1), (6, 5, 4)]):
        toks = rng.randint(0, cfg.vocab_size, size=plen).astype(np.int32)
        reqs.append(Request(rid=f"q{i}", tokens=toks,
                            max_new_tokens=gen, arrival=arr))
        eng.submit(reqs[-1])
    finished = eng.run(max_steps=200)
    assert sorted(finished) == [f"q{i}" for i in range(4)]
    for r in reqs:
        ref, _ = _reference_generate(model, params, r.tokens,
                                     r.max_new_tokens, max_len)
        assert finished[r.rid].generated == ref, r.rid


def test_engine_slot_recycling_and_eos():
    """EOS early exit frees the slot; a waiting request recycles it."""
    cfg, model, params = _model()
    # pick the token the model actually emits first as the EOS id so the
    # request terminates on step one
    rng = np.random.RandomState(1)
    toks = rng.randint(0, cfg.vocab_size, size=6).astype(np.int32)
    ref, _ = _reference_generate(model, params, toks, 1, 32)
    eos = ref[0]

    eng = ServeEngine(model, params, num_slots=1, max_len=32, eos_id=eos)
    eng.submit(Request(rid="a", tokens=toks, max_new_tokens=50))
    other = rng.randint(0, cfg.vocab_size, size=4).astype(np.int32)
    eng.submit(Request(rid="b", tokens=other, max_new_tokens=3))
    finished = eng.run(max_steps=100)
    assert finished["a"].generated == [eos]        # stopped at EOS
    assert len(finished["b"].generated) <= 3
    assert finished["b"].prefill_step >= finished["a"].finish_step


def test_engine_throughput_accounting_live_slots_only():
    """Prefill and decode tokens are tracked separately; decode counts
    live slots only (idle ticks do not inflate throughput)."""
    cfg, model, params = _model()
    eng = ServeEngine(model, params, num_slots=2, max_len=32)
    rng = np.random.RandomState(2)
    plens, gens = [6, 4], [2, 8]
    for i, (plen, gen) in enumerate(zip(plens, gens)):
        eng.submit(Request(
            rid=f"t{i}",
            tokens=rng.randint(0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=gen))
    eng.run(max_steps=100)
    assert eng.stats["prefill_tokens"] == sum(plens)
    # first token of each request comes from its prefill; every later
    # token is one live decode tick
    assert eng.stats["decode_tokens"] == sum(g - 1 for g in gens)
    # the batch kept ticking after t0 finished: ticks > live decode work
    assert eng.stats["ticks"] >= max(gens) - 1
    tp = eng.throughput()
    assert tp["prefill_tok_s"] > 0 and tp["decode_tok_s"] > 0


# ----------------------------------------------------------------------
# Serve-side Tier-3 detectors
# ----------------------------------------------------------------------
def test_engine_detectors_flag_injected_kv_waste():
    """Injected waste: a duplicated prompt (prefix-cache opportunity) and
    an early-finishing request whose slot idles while the batch keeps
    decoding (dead + silent KV stores)."""
    cfg, model, params = _model()
    det = ServingDetectors(ProfilerConfig(enabled=True, num_watchpoints=8,
                                          seed=0), sites_per_step=4)
    eng = ServeEngine(model, params, num_slots=2, max_len=48,
                      detectors=det)
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, size=8).astype(np.int32)
    # slot waste: w0 finishes after 2 tokens, w1 keeps the batch running
    eng.submit(Request(rid="w0", tokens=shared, max_new_tokens=2))
    eng.submit(Request(rid="w1", tokens=shared.copy(),    # duplicate prompt
                       max_new_tokens=30))
    eng.run(max_steps=200)

    rep = det.report
    fr = rep.fractions()
    kinds = {f.kind for f in rep.findings}
    # duplicated prompt: the second admission re-loads w0's prefix
    assert "silent_prefix_load" in kinds
    dup = [f for f in rep.findings if f.kind == "silent_prefix_load"]
    assert any("req:w0" in " ".join(f.c1) and "req:w1" in " ".join(f.c2)
               for f in dup)
    # w0's idle slot is rewritten every tick: dead stores (no live
    # request) whose values are identical (silent) — both trapped
    assert "dead_kv_store" in kinds
    assert fr["dead_kv_store"] > 0
    assert "silent_kv_store" in kinds, fr
    assert fr["silent_kv_store"] > 0.5, fr
    dead = [f for f in rep.findings if f.kind == "dead_kv_store"]
    assert all(len(f.c1) >= 1 and len(f.c2) >= 1 for f in dead)
    # ⟨C1,C2⟩: armed on the KV row, trapped at an engine step
    assert any("serve.kv" in f.c1[0] for f in dead)
    assert any(any("serve.engine" in c for c in f.c2) for f in dead)


def test_paged_mode_eliminates_detected_kv_waste():
    """The closed detect→optimize loop (ISSUE 3 acceptance): on the
    duplicated-prefix workload the dense layout's detectors flag silent
    prefix loads and dead/silent KV stores; the paged layout turns the
    prefixes into cache hits and drops idle/finished-slot writes, so the
    same detectors must report strictly lower waste fractions — while
    greedy outputs stay identical (covered in test_kv_cache)."""
    cfg, model, params = _model()
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, size=12).astype(np.int32)

    def run(kvl):
        det = ServingDetectors(ProfilerConfig(enabled=True,
                                              num_watchpoints=8, seed=0),
                               sites_per_step=4)
        eng = ServeEngine(model, params, num_slots=2, max_len=48,
                          detectors=det, kv_layout=kvl, page_size=16)
        # three requests sharing a 12-token prefix, staggered so each
        # admission can reuse the previous prefill's pages; w0 finishes
        # early and its slot idles while w1 keeps the batch decoding
        for i, (gen, arr) in enumerate([(2, 0), (20, 2), (4, 4)]):
            tail = rng.randint(0, cfg.vocab_size, size=8).astype(np.int32)
            eng.submit(Request(rid=f"w{i}",
                               tokens=np.concatenate([shared, tail]),
                               max_new_tokens=gen, arrival=arr))
        eng.run(max_steps=200)
        return det.report.fractions(), eng.stats

    rng_state = rng.get_state()
    fr_dense, st_dense = run("dense")
    rng.set_state(rng_state)               # identical prompt tails
    fr_paged, st_paged = run("paged")

    # dense flags the waste...
    assert fr_dense["silent_prefix_load"] > 0
    assert fr_dense.get("dead_kv_store", 0) > 0
    # ...paged eliminates it: strictly lower where dense flagged, and
    # never higher anywhere
    assert (fr_paged.get("silent_prefix_load", 0.0)
            < fr_dense["silent_prefix_load"]), (fr_dense, fr_paged)
    assert (fr_paged.get("dead_kv_store", 0.0)
            < fr_dense["dead_kv_store"]), (fr_dense, fr_paged)
    assert (fr_paged.get("silent_kv_store", 0.0)
            <= fr_dense.get("silent_kv_store", 0.0)), (fr_dense, fr_paged)
    # the eliminated Def.-3 waste shows up as prefix-cache hits instead
    assert st_paged["prefix_hits"] >= 1
    assert st_dense["prefix_hits"] == 0
    assert (st_paged["prefill_computed_tokens"]
            < st_dense["prefill_computed_tokens"])


def test_paged_detector_traps_survive_page_free():
    """Stale traps disarm on page free (the substrate's out-of-extent
    rule): after a heavy paged run with recycling, no armed watchpoint
    may reference a page that is currently unallocated."""
    cfg, model, params = _model()
    det = ServingDetectors(ProfilerConfig(enabled=True, num_watchpoints=8,
                                          seed=1), sites_per_step=4)
    eng = ServeEngine(model, params, num_slots=2, max_len=32,
                      detectors=det, kv_layout="paged", page_size=8)
    rng = np.random.RandomState(9)
    for i in range(6):
        eng.submit(Request(
            rid=f"s{i}",
            tokens=rng.randint(0, cfg.vocab_size,
                               size=rng.randint(4, 12)).astype(np.int32),
            max_new_tokens=1 + i % 3, arrival=i))
    eng.run(max_steps=200)
    eng.kv.check()
    allocated = {p for p in range(eng.kv.num_pages)
                 if eng.kv.alloc.refcount[p] > 0}
    for wp in det.wp.armed():
        assert wp.meta["page"] in allocated, wp.meta


def test_engine_rejects_unindexed_families():
    cfg = registry.get_config("zamba2-1.2b").smoke()
    model = build_model(cfg)
    params = model.init(KEY)
    with pytest.raises(ValueError):
        ServeEngine(model, params, num_slots=2, max_len=16)


def test_engine_stats_monotonic_across_generations():
    """Satellite of the fleet tier: the router and the fleet benchmarks
    aggregate per-replica counters by snapshot deltas, which silently
    undercounts if any counter ever decreases (the historical symptom:
    padded_prefill_tokens zeroed between waves). Stats are now
    `MonotonicStats`: every numeric key is non-decreasing across full
    serve generations with slot recycling, and an explicit decrement
    raises instead of corrupting fleet accounting."""
    cfg, model, params = _model()
    eng = ServeEngine(model, params, num_slots=2, max_len=24,
                      kv_layout="paged", page_size=8)
    rng = np.random.RandomState(3)
    snap = dict(eng.stats)
    for wave in range(3):
        for b in range(3):     # 3 requests > 2 slots: recycling each wave
            eng.submit(Request(
                rid=f"w{wave}r{b}",
                tokens=rng.randint(0, cfg.vocab_size,
                                   size=rng.randint(6, 16)).astype(np.int32),
                max_new_tokens=2 + b))
        eng.run(max_steps=300)
        for k, v in snap.items():
            if isinstance(v, (int, float)):
                assert eng.stats[k] >= v, \
                    f"stat {k} decreased across generations: {v} -> " \
                    f"{eng.stats[k]}"
        snap = dict(eng.stats)
    assert eng.stats["prefill_tokens"] > 0 and eng.stats["ticks"] > 0

    with pytest.raises(ValueError, match="may not decrease"):
        eng.stats["ticks"] = eng.stats["ticks"] - 1
    eng.stats["ticks"] = eng.stats["ticks"]          # equal is fine
    eng.stats["new_gauge"] = 1.5                     # fresh keys are fine
    before = dict(eng.stats)
    eng.stats["new_gauge"] += 1
    assert eng.stats["new_gauge"] == 2.5 and before["new_gauge"] == 1.5


@pytest.mark.parametrize("jxperf", [False, True])
def test_step_phase_counters(jxperf):
    """Every `step()` counts itself and its phases' host seconds; the
    seconds spent in calls made for JXPerf (`jxperf_s`) are counted only
    when its detectors and kernel counters are attached."""
    cfg, model, params = _model()
    det = (ServingDetectors(ProfilerConfig(enabled=True, seed=0))
           if jxperf else None)
    eng = ServeEngine(model, params, num_slots=2, max_len=32,
                      kv_layout="paged", page_size=8, detectors=det,
                      kernel_counters=jxperf)
    rng = np.random.RandomState(5)
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", max_new_tokens=3,
                           tokens=rng.randint(0, cfg.vocab_size, 6 + i)
                           .astype(np.int32)))
    eng.run(max_steps=50)
    st = eng.stats
    assert st["steps"] == eng.step_no > 0
    assert st["step_s"] >= (st["admit_s"] + st["prefill_s"]
                            + st["decode_s"] + st["accept_s"])
    assert 0 < st["step_cpu_s"]
    assert (st["jxperf_s"] > 0) == jxperf
    assert st["jxperf_s"] <= st["step_s"]


@pytest.mark.parametrize("mode", ["admit", "decode", "spec", "jxperf"])
def test_engine_step_donates_cache(mode):
    """Every engine program donates the cache it is handed: after a
    `step()` the pool leaves of the cache the step started from are
    deleted (one pool is live, not two), and the engine serves on from
    the cache each call hands back — with the detectors' reads of
    `self.cache` after the calls (``jxperf``) and the speculative
    verify (``spec``) too."""
    from repro.serve.spec import NGramDrafter
    cfg, model, params = _model()
    det = (ServingDetectors(ProfilerConfig(enabled=True, seed=0))
           if mode == "jxperf" else None)
    eng = ServeEngine(model, params, num_slots=2, max_len=32,
                      kv_layout="paged", page_size=8, detectors=det,
                      kernel_counters=mode == "jxperf",
                      drafter=NGramDrafter() if mode == "spec" else None)
    rng = np.random.RandomState(7)
    for i in range(2):
        eng.submit(Request(rid=f"r{i}", max_new_tokens=6,
                           tokens=rng.randint(0, cfg.vocab_size, 9)
                           .astype(np.int32)))
    if mode != "admit":
        eng.step()                       # admit both: the rest decodes
    before = eng.cache
    eng.step()
    assert eng.stats["prefills"] == 1    # one admission, before or here
    for sub in before["main"].values():
        assert sub["k"].is_deleted() and sub["v"].is_deleted()
    for sub in eng.cache["main"].values():
        assert not sub["k"].is_deleted()
    fin = eng.run(max_steps=50)
    assert sorted(fin) == ["r0", "r1"]
    assert all(len(r.generated) == 6 for r in fin.values())

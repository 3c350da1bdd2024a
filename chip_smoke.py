#!/usr/bin/env python3
"""Bring-up proof on a TPU: the main path at qwen3-1.7b's published widths.

    python3 chip_smoke.py               # one chip: serve, kernels, train
    python3 chip_smoke.py --four-chips  # four chips: fsdp train only

One chip (the default) runs three phases, each through the entry points a
user calls, with weights made from ``--seed``:

* serve — `ServeEngine` built as `launch/serve.py` builds it, on the paged
  KV pool with in-kernel store counters (tier 4) and the serving
  detectors (tier 3) attached, at full width and depth. 8 requests of
  128-512 prompt tokens (two share a 256-token prefix) generate 32 tokens
  each; then the same 8 run again with the n-gram drafter and rollback,
  so the window kernel runs in store (prefill) and defer (verify) mode.
* kernels — the Pallas paged decode and window kernels, compiled, against
  the `kernels/ref.py` compositions at the same widths: outputs within
  `KERNEL_TOL`, pool contents and store-site counters exactly.
* train — 3 steps of the train step at qwen3-1.7b widths with the depth
  cut to what one chip holds (AdamW state is 14 B/param).

``--four-chips`` runs only the sharded path and what it is compared with:
the 4-layer cut on one chip against the same on a 4-chip fsdp mesh
(losses within `LOSS_TOL`), then the full 28 layers under fsdp.

No phase catches its own failure. Any failure exits non-zero before the
last line, which is ``{"ok": true, "device": {...}}`` as JAX reports the
device. Without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-1.7b"
# Pallas kernel outputs against the ref compositions. TPU matmuls on f32
# operands at default precision take one bf16 pass (8-bit mantissa,
# relative rounding 2^-9), and the engine's activations are bf16: scores
# and the softmax-weighted sums of unit-variance values carry ~1e-2
# absolute error on either side. 2e-2 bounds that; a wrong page, mask or
# row moves outputs by O(1).
KERNEL_TOL = 2e-2
# 1-chip vs 4-chip train losses (~12 at init). Same math, but bf16
# activations round differently once XLA fuses a per-device batch of 1
# instead of 4, and the loss mean is reduced across chips: 1e-2 is ~0.1%
# of the loss, well above that noise and far below a wrong shard (which
# moves the loss by O(1)).
LOSS_TOL = 1e-2
SERVE_SLOTS, SERVE_GEN, SPEC_K, PAGE = 4, 32, 4, 16
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 4, 1024, 3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Seconds JAX spends in backend compiles (cache retrievals included),
    read per phase from JAX's own monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs, self.count = 0.0, 0

    def __call__(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.secs += duration_secs
            self.count += 1


def phase(name, clock, fn, *args, **kw):
    import jax
    s0, n0, t0 = clock.secs, clock.count, time.perf_counter()
    out = fn(*args, **kw)
    # (the CPU backend of a rehearsal reports no memory stats)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    log(f"[{name}] done in {time.perf_counter() - t0:.1f} s, of which "
        f"compile {clock.secs - s0:.1f} s over {clock.count - n0} programs; "
        f"peak bytes in use per device so far: {peaks}")
    return out


# ---------------------------------------------------------------- serve
def make_prompts(vocab: int, seed: int, lengths=(512, 128, 200, 320, 384,
                                                 448, 160, 288),
                 shared=(3, 4), prefix_len: int = 256):
    """8 prompts of 128-512 tokens; the two in `shared` start with the
    same `prefix_len` tokens and land in different admission groups, so
    the second maps the first's pages from the prefix index. The donor
    is the last of the first group to register, so its entries are the
    freshest in the LRU-bounded index when the second is admitted."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in lengths]
    for i in shared[1:]:
        prompts[i][:prefix_len] = prompts[shared[0]][:prefix_len]
    return prompts


def run_engine(model, params, prompts, *, drafter, max_len, seed):
    import jax.numpy as jnp
    from repro.configs.base import ProfilerConfig
    from repro.core.detectors import ServingDetectors
    from repro.serve.engine import Request, ServeEngine
    det = ServingDetectors(ProfilerConfig(enabled=True, seed=seed))
    eng = ServeEngine(model, params, num_slots=SERVE_SLOTS, max_len=max_len,
                      detectors=det, kv_dtype=jnp.float32,
                      kv_layout="paged", page_size=PAGE,
                      drafter=drafter, spec_k=SPEC_K, spec_rollback=True,
                      kernel_counters=True, owner="serve")
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"r{i}", tokens=p, max_new_tokens=SERVE_GEN))
    fin = eng.run()
    return eng, det, fin


def report_engine(tag, eng, det, fin, n, vocab):
    st = eng.stats
    gen = [fin[f"r{i}"].generated for i in range(n) if f"r{i}" in fin]
    kern = det.kernel
    log(f"[serve:{tag}] requests finished {len(fin)} of {n}, tokens "
        f"generated {sum(len(g) for g in gen)}, prefix hits "
        f"{st['prefix_hits']} ({st['prefix_hit_tokens']} tokens), drafts "
        f"accepted {st['draft_accepted']} of {st['draft_proposed']}, "
        f"prefills {st['prefills']}, ticks {st['ticks']}")
    log(f"[serve:{tag}] tier-4 totals {dict(kern.totals)} checked "
        f"{dict(kern.checked)} flagged {dict(kern.flagged)}")
    check(len(fin) == n, f"{tag}: {len(fin)} of {n} requests finished")
    check(all(len(g) == SERVE_GEN for g in gen),
          f"{tag}: a request stopped short of {SERVE_GEN} tokens")
    check(all(0 <= t < vocab for g in gen for t in g),
          f"{tag}: a generated token is outside the vocabulary")
    check(st["prefix_hits"] >= 1, f"{tag}: the shared prefix never hit")
    check(kern.totals.get("kernel_store_elems", 0) > 0,
          f"{tag}: the kernels counted no stores")
    return [list(g) for g in gen]


def serve_phase(cfg, seed):
    import jax
    from repro.models.zoo import build_model
    from repro.serve.spec import make_drafter
    model = build_model(cfg)
    # cast once: both engines below hold this one compute-dtype tree
    params = model.compute_params(model.init(jax.random.PRNGKey(seed)))
    prompts = make_prompts(cfg.vocab_size, seed)
    n = len(prompts)
    max_len = max(p.size for p in prompts) + SERVE_GEN + SPEC_K + 1

    eng, det, fin = run_engine(model, params, prompts, drafter=None,
                               max_len=max_len, seed=seed)
    plain = report_engine("plain", eng, det, fin, n, cfg.vocab_size)
    # the first admission group's prefill shape: the longest prompt
    # padded to a power of two (the engine's bucket), within max_len
    bucket = min(1 << (max(p.size for p in prompts) - 1).bit_length(),
                 max_len)
    tick = eng.lowered_tick().compile().as_text().count("tpu_custom_call")
    pre = eng.lowered_prefill(bucket).compile().as_text().count(
        "tpu_custom_call")
    log(f"[serve] tpu_custom_call in the compiled tick: {tick}, "
        f"in the compiled prefill ({bucket}): {pre}")
    check(tick > 0 and pre > 0, "no Pallas kernel in the compiled programs")
    del eng

    # the drafter has seen the first pass (a long-lived server's corpus)
    drafter = make_drafter("ngram")
    for p, g in zip(prompts, plain):
        drafter.observe(np.concatenate([p, np.asarray(g, np.int32)]))
    eng, det, fin = run_engine(model, params, prompts, drafter=drafter,
                               max_len=max_len, seed=seed)
    spec = report_engine("spec", eng, det, fin, n, cfg.vocab_size)
    fr = det.kernel.fractions()
    same = sum(a == b for x, y in zip(plain, spec) for a, b in zip(x, y))
    log(f"[serve:spec] tokens equal to the plain pass: {same} of "
        f"{n * SERVE_GEN}; kernel-tier rejected-draft-store fraction "
        f"{fr.get('kernel_rejected_draft_store')}")
    check(eng.stats["draft_accepted"] > 0, "spec: no draft accepted")
    check(det.kernel.checked.get("kernel_rejected_draft_store", 0) > 0
          and fr["kernel_rejected_draft_store"] == 0.0,
          "spec: rollback stored rejected drafts")


# -------------------------------------------------------------- kernels
def hostile_table(rng, B, M, P, ps, used):
    """Out-of-order pages per slot, unmapped tails, an idle slot
    (`used[b] == 0`)."""
    pt = rng.permutation(P)[:B * M].reshape(B, M).astype(np.int32)
    for b, u in enumerate(used):
        pt[b, u:] = -1
    return pt


def compare(tag, got, want, live=None, exact=False):
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if live is not None:
        g, w = g[live], w[live]
    if exact:
        ok = np.array_equal(g, w)
        log(f"[kernels] {tag}: exact {ok}")
    else:
        err = float(np.max(np.abs(g - w)))
        ok = bool(np.allclose(g, w, atol=KERNEL_TOL, rtol=KERNEL_TOL))
        log(f"[kernels] {tag}: max abs err {err:.3e} (tol {KERNEL_TOL}) "
            f"within {ok}")
    check(ok, f"kernels: {tag}")


def kernel_phase(cfg, seed, interpret=False):
    import jax
    import jax.numpy as jnp
    from functools import partial
    from repro.kernels import ref as kref
    from repro.kernels.flash_prefill import paged_window_attention
    from repro.kernels.paged_attention import paged_decode_attention
    B, Hq, Hkv, D = 4, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ps, M = PAGE, 36
    P = B * M + 4
    rng = np.random.RandomState(seed)
    used = [M, 20, 9, 0]
    pt = jnp.asarray(hostile_table(rng, B, M, P, ps, used))
    pool_k = jnp.asarray(rng.randn(P, ps, Hkv, D), jnp.float32)
    pool_v = jnp.asarray(rng.randn(P, ps, Hkv, D), jnp.float32)

    def rows(S):
        return (jnp.asarray(rng.randn(B, S, Hq, D), jnp.bfloat16),
                jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16),
                jnp.asarray(rng.randn(B, S, Hkv, D), jnp.bfloat16))

    # decode: one new row per slot, partially filled last pages
    idx = jnp.asarray([u * ps - 1 - rng.randint(0, ps) if u else -1
                       for u in used], jnp.int32)
    live = np.asarray(idx) >= 0
    q, kn, vn = rows(1)
    dec = jax.jit(partial(paged_decode_attention, interpret=interpret))
    dec_ref = jax.jit(kref.paged_decode_ref)
    out, lse, cnt = dec(q, kn, vn, pool_k, pool_v, pt, idx)
    want, ck, cv, cnt_r = dec_ref(q, kn, vn, pool_k, pool_v, pt, idx)
    compare("decode out", out, want, live)
    compare("decode counters", cnt, cnt_r, exact=True)
    check(np.isfinite(np.asarray(lse)[live]).all(), "decode lse finite")
    # the same rows again onto the stored pool: every element silent
    _, _, cnt2 = dec(q, kn, vn, ck, cv, pt, idx)
    _, _, _, cnt2_r = dec_ref(q, kn, vn, ck, cv, pt, idx)
    compare("decode silent re-store counters", cnt2, cnt2_r, exact=True)
    check(int(np.asarray(cnt2)[live, 1].sum()) > 0, "silent stores counted")

    # window: prefill width (store) and verify width (store and defer);
    # slot 1's prefill window runs past its table (dropped rows)
    # (an idle slot sits below -S, so every window position is negative)
    for S, starts in ((128, [0, 17 * ps + 3, 5, -129]),
                      (SPEC_K + 1, [30 * ps + 7, 14 * ps, 3 * ps + 9, -6])):
        start = jnp.asarray(starts, jnp.int32)
        live = np.asarray(start) >= 0
        q, kw, vw = rows(S)
        for store in (True, False):
            tag = f"window S={S} {'store' if store else 'defer'}"
            win = jax.jit(partial(paged_window_attention, store=store,
                                  interpret=interpret))
            win_ref = jax.jit(partial(kref.paged_window_ref, store=store))
            out, _, cnt, npk, npv = win(q, kw, vw, pool_k, pool_v, pt, start)
            want, ck, cv, cnt_r = win_ref(q, kw, vw, pool_k, pool_v, pt,
                                          start)
            compare(f"{tag} out", out, want, live)
            compare(f"{tag} pool k", npk, ck, exact=True)
            compare(f"{tag} pool v", npv, cv, exact=True)
            compare(f"{tag} counters", cnt, cnt_r, exact=True)
            log(f"[kernels] {tag} counters [stored, silent, dropped] per "
                f"slot: {np.asarray(cnt).tolist()}")


# ---------------------------------------------------------------- train
def train_losses(cfg, strategy, seed, tag):
    import jax
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import batch_at
    from repro.launch.train import jit_train_step
    from repro.models.zoo import build_model
    from repro.train import state as TS
    model = build_model(cfg)
    tc = TrainConfig(learning_rate=3e-4, total_steps=TRAIN_STEPS,
                     warmup_steps=1, seed=seed)
    jit_step, to_device = jit_train_step(model, tc, strategy)
    state = TS.create(model, jax.random.PRNGKey(seed), strategy=strategy)
    batches = [to_device(batch_at(cfg, TRAIN_BATCH, TRAIN_SEQ, seed=seed,
                                  step=i)) for i in range(TRAIN_STEPS)]
    compiled = jit_step.lower(state, batches[0]).compile()
    ma = compiled.memory_analysis()
    log(f"[train:{tag}] compiled step per device: arguments "
        f"{ma.argument_size_in_bytes} B, outputs {ma.output_size_in_bytes} "
        f"B, temporaries {ma.temp_size_in_bytes} B, aliased "
        f"{ma.alias_size_in_bytes} B")
    losses = []
    for b in batches:
        state, metrics = compiled(state, b)
        losses.append(float(metrics["loss"]))
    log(f"[train:{tag}] losses {losses}")
    check(all(np.isfinite(losses)), f"train {tag}: non-finite loss")
    return losses


def cut(cfg):
    reduced = {"num_layers": f"{cfg.num_layers} -> {TRAIN_LAYERS}"}
    log(f"[train] reduced: {json.dumps(reduced)} (widths as published; "
        f"batch {TRAIN_BATCH} x seq {TRAIN_SEQ})")
    return dataclasses.replace(cfg, num_layers=TRAIN_LAYERS)


def four_chip_phase(cfg, seed):
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import make_strategy
    small = cut(cfg)
    with jax.default_device(jax.devices()[0]):
        one = train_losses(small, None, seed, "4 layers, 1 chip")
    fsdp = make_strategy("fsdp", make_host_mesh())
    four = train_losses(small, fsdp, seed, "4 layers, 4 chips fsdp")
    diff = max(abs(a - b) for a, b in zip(one, four))
    log(f"[train] 1 chip vs 4 chips: max |loss diff| {diff:.3e} "
        f"(tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, "1-chip and 4-chip losses disagree")
    train_losses(cfg, fsdp, seed, f"{cfg.num_layers} layers, 4 chips fsdp")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip fsdp train path and its "
                         "1-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    want = 4 if a.four_chips else 1
    check(devs[0].platform == "tpu",
          f"needs a TPU; JAX found {devs[0].platform}")
    check(len(devs) >= want, f"needs {want} chips; JAX found {len(devs)}")
    from repro.configs import registry
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"[smoke] {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    cfg = registry.get_config(ARCH)

    if a.four_chips:
        phase("train", clock, four_chip_phase, cfg, a.seed)
    else:
        phase("serve", clock, serve_phase, cfg, a.seed)
        phase("kernels", clock, kernel_phase, cfg, a.seed)
        phase("train", clock, lambda: train_losses(
            cut(cfg), None, a.seed, f"{TRAIN_LAYERS} layers, 1 chip"))
    log(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()

"""Serving driver: continuous-batching engine with batched prefill,
KV-cache waste detectors, and honest prefill-vs-decode accounting.

CPU smoke:  PYTHONPATH=src python -m repro.launch.serve \
                --arch qwen3-1.7b --smoke --batch 4 --prompt-len 32 --gen 16

Dense/MoE families run on `serve.engine.ServeEngine` (single-pass
batched prefill + per-slot decode positions + slot recycling); families
without an indexed KV cache in every block (hybrid/ssm/vlm/audio) fall
back to the legacy token-loop, with prefill and decode still timed
separately.

``--kv paged`` switches the engine to the block-paged KV heap
(serve/kv_cache.py): refcounted pages + copy-on-write prefix reuse,
eliminating exactly the waste the detectors flag in dense mode —
idle-slot dead/silent KV stores and silent prefix loads.

``--spec on`` adds speculative decoding (serve/spec.py): a host-side
drafter proposes up to ``--spec-k`` tokens per tick and ONE width-(k+1)
verify forward accepts the greedy-consistent prefix, so outputs stay
bit-identical to plain decode while live slots emit up to k+1 tokens
per tick. Rejected drafts are Def.-1 dead KV stores — measured by the
``rejected_draft_store`` detector site, and eliminated in the paged
layout by ``--spec-rollback on`` (the commit stops at the accept
point). ``--draft oracle`` runs a plain pass first and replays its
continuations (accept-rate 1.0 — the mechanism's upper bound and a live
bit-identity assertion).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import ProfilerConfig
from repro.core.detectors import ServingDetectors
from repro.core.findings import Finding, WasteProfile, merge_profiles
from repro.core.hlo_waste import analyze_waste
from repro.core.interpreter import profile_fn
from repro.core.report import dump_json
from repro.core.sarif import write_sarif
from repro.data.synthetic import batch_at
from repro.models.zoo import build_model
from repro.serve.decode import make_serve_step
from repro.serve.engine import ENGINE_FAMILIES, Request, ServeEngine
from repro.serve.spec import make_drafter
from repro.runtime.compile_cache import enable_compile_cache


def padding_waste_profile(stats) -> WasteProfile:
    """Tier-2-style padding-waste finding from the engine's accounting:
    `_bucket`'s power-of-two prompt padding silently burns prefill
    compute on garbage positions (checked = all prefill positions
    swept, flagged = the padded ones)."""
    prof = WasteProfile(tier=2)
    padded = int(stats.get("padded_prefill_tokens", 0))
    useful = int(stats.get("prefill_computed_tokens", 0))
    prof.checked["prefill_padding"] = padded + useful
    prof.flagged["prefill_padding"] = padded
    if padded:
        prof.add(Finding(
            kind="prefill_padding", tier=2,
            c1=("serve.engine:_bucket",), c2=("serve.engine:prefill",),
            count=int(stats.get("prefills", 0)),
            fraction=padded / max(padded + useful, 1),
            meta={"padded_tokens": padded, "computed_tokens": useful}))
    return prof


def _run_engine(cfg, model, params, prompts, gen, seed, profile,
                kv="dense", page_size=16, spec=False, spec_k=4,
                draft="ngram", spec_rollback=True, obj_registry=None):
    batch, prompt_len = prompts.shape
    max_len = prompt_len + gen + 1

    def build_and_run(drafter, det, reg=None):
        eng = ServeEngine(model, params, num_slots=batch, max_len=max_len,
                          detectors=det, kv_dtype=jnp.float32,
                          kv_layout=kv, page_size=page_size,
                          drafter=drafter, spec_k=spec_k,
                          spec_rollback=spec_rollback,
                          registry=reg, owner="serve")
        for b in range(batch):
            eng.submit(Request(rid=f"r{b}", tokens=np.asarray(prompts[b]),
                               max_new_tokens=gen))
        eng.run()
        out = np.stack(
            [np.asarray(eng.finished[f"r{b}"].generated[:gen], np.int32)
             for b in range(batch)])
        return eng, out

    drafter = None
    plain_out = None
    if spec:
        if draft == "oracle":
            # harvest the plain greedy continuations first; the replay
            # drafter then proposes exactly them (accept-rate 1.0) —
            # the upper bound of the verify/rollback machinery, and a
            # live bit-identity check of the acceptance rule
            _, plain_out = build_and_run(None, None)
            seqs = [np.concatenate([np.asarray(prompts[b]), plain_out[b]])
                    for b in range(batch)]
            drafter = make_drafter("oracle", sequences=seqs)
        else:
            drafter = make_drafter(draft, model=model, params=params)
    det = ServingDetectors(ProfilerConfig(enabled=True, seed=seed)) \
        if profile else None
    # only the measured engine registers objects: the oracle's plain
    # pre-pass would otherwise leave a dead engine's pages in the scan
    eng, out = build_and_run(drafter, det, obj_registry)
    if plain_out is not None:
        assert np.array_equal(out, plain_out), \
            "speculative outputs diverged from plain greedy decode"
    tp = eng.throughput()
    tier3 = det.report if det is not None else None
    tier2_subject = eng.lowered_tick() if profile else None
    return jnp.asarray(out), tp, tier3, tier2_subject, eng.stats


def _bucket_pow2(n: int, cap: int, lo: int = 8) -> int:
    """Smallest power-of-two >= n (engine `_bucket` policy), capped."""
    b = lo
    while b < n:
        b *= 2
    return min(b, cap)


def encoder_padding_profile(stats) -> WasteProfile:
    """Tier-2 padding-waste finding for encoder-decoder serving: frames
    padded to the run extent burn encoder prefill compute and cross-KV
    bytes on garbage rows (checked = all frame rows swept, flagged =
    the padded ones). Bucketing the extent (``--bucket-frames``) is the
    fix this finding's bytes measure."""
    prof = WasteProfile(tier=2)
    padded = int(stats.get("padded_frames", 0))
    true = int(stats.get("true_frames", 0))
    prof.checked["prefill_padding"] = padded + true
    prof.flagged["prefill_padding"] = padded
    if padded:
        prof.add(Finding(
            kind="prefill_padding", tier=2,
            c1=("launch.serve:_run_legacy",), c2=("models.lm:encode",),
            count=1, bytes=float(stats.get("padded_bytes", 0)),
            fraction=padded / max(padded + true, 1),
            meta={"padded_frames": padded, "true_frames": true,
                  "frames_run": int(stats.get("frames_run", 0)),
                  "frames_capacity": int(stats.get("frames_capacity", 0))}))
    return prof


def _prep_frames(cfg, model, kw, frame_lengths, bucket_frames):
    """Right-pad audio frames to the run extent and account the padding.

    Baseline: every request runs at the full capacity extent (the
    frames buffer as generated). Bucketed: the extent shrinks to the
    power-of-two bucket of the batch's longest true length. Rows past
    each true length are zeroed and masked (kv_valid through the
    encoder, xvalid through cross-attention), so greedy outputs are
    identical in both modes — only the padded bytes differ."""
    frames = np.asarray(kw["frames"])
    B, cap = frames.shape[:2]
    lens = np.minimum(np.asarray(frame_lengths, np.int32), cap)
    F_run = cap if not bucket_frames \
        else _bucket_pow2(int(lens.max()), cap)
    mask = np.arange(cap)[None, :] < lens[:, None]
    frames = np.where(mask[..., None], frames, 0.0)[:, :F_run]
    kw = {**kw, "frames": jnp.asarray(frames),
          "frame_lengths": jnp.asarray(lens)}
    true = int(lens.sum())
    padded = B * F_run - true
    itemsize = 4  # float32 frames and kv_dtype below
    # a padded frame row costs its embedding row plus the per-layer
    # cross-K/V rows precomputed from it
    row = cfg.d_model * itemsize
    kv_row = model.sched.n_super * 2 * cfg.num_kv_heads * cfg.head_dim \
        * itemsize
    stats = {"frames_capacity": cap, "frames_run": F_run,
             "true_frames": true, "padded_frames": padded,
             "padded_bytes": padded * (row + kv_row)}
    return kw, stats


def _run_legacy(cfg, model, params, prompts, gen, kw, *,
                frame_lengths=None, bucket_frames=False):
    """Token-loop driver for families without an indexed KV cache."""
    batch, prompt_len = prompts.shape
    max_len = prompt_len + gen + 1
    stats = None
    if cfg.family == "audio" and frame_lengths is not None:
        kw, stats = _prep_frames(cfg, model, kw, frame_lengths,
                                 bucket_frames)
    cache = model.init_cache(params, batch, max_len,
                             kv_dtype=jnp.float32, **kw)
    # init_cache needs the full tree (cross-KV precompute); the decode
    # loop gets the decode-path view so the jitted step carries no dead
    # encoder/cross-KV invars (tier-0 dead_param, whisper/vision)
    params = model.decode_params(params)
    serve_step = jax.jit(make_serve_step(model), donate_argnums=(1,))

    t0 = time.perf_counter()
    for t in range(prompt_len):
        nxt, cache = serve_step(params, cache, prompts[:, t:t + 1])
    nxt.block_until_ready()
    t_prefill = time.perf_counter() - t0

    t0 = time.perf_counter()
    generated = [nxt]
    for _ in range(gen - 1):
        nxt, cache = serve_step(params, cache, generated[-1])
        generated.append(nxt)
    nxt.block_until_ready()
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(generated, axis=1)
    tp = {"prefill_tok_s": batch * prompt_len / max(t_prefill, 1e-9),
          "decode_tok_s": batch * gen / max(t_decode, 1e-9)}
    lowered = serve_step.lower(params, cache, generated[-1])
    return out, tp, cache, lowered, stats


def run(arch: str, *, smoke: bool = True, batch: int = 4,
        prompt_len: int = 32, gen: int = 16, seed: int = 0,
        profile: bool = False, profile_out: str = None,
        sarif_out: str = None,
        kv: str = "dense", page_size: int = 16,
        spec: bool = False, spec_k: int = 4, draft: str = "ngram",
        spec_rollback: bool = True, objects: bool = False,
        bucket_frames: bool = True):
    cfg = registry.get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    obj_registry = None
    if objects:
        from repro.core.objects import ObjectRegistry, register_tree
        obj_registry = ObjectRegistry()
        register_tree(obj_registry, "serve/params", params)

    data = batch_at(cfg, batch, prompt_len, seed=seed, step=0)
    prompts = jnp.asarray(data["tokens"])
    kw = {}
    if cfg.family == "vlm":
        kw["img"] = jnp.asarray(data["img"])
    if cfg.family == "audio":
        kw["frames"] = jnp.asarray(data["frames"])

    tier3 = None
    stats = None
    enc_stats = None
    if cfg.family in ENGINE_FAMILIES:
        out, tp, tier3, tier2_subject, stats = _run_engine(
            cfg, model, params, prompts, gen, seed, profile,
            kv=kv, page_size=page_size, spec=spec, spec_k=spec_k,
            draft=draft, spec_rollback=spec_rollback,
            obj_registry=obj_registry)
    else:
        if kv != "dense":
            raise ValueError(f"--kv paged needs the engine families "
                             f"{ENGINE_FAMILIES}, not {cfg.family!r}")
        if spec:
            raise ValueError(f"--spec needs the engine families "
                             f"{ENGINE_FAMILIES}, not {cfg.family!r}")
        lens = None
        if cfg.family == "audio":
            from repro.data.synthetic import frame_lengths
            lens = frame_lengths(cfg, batch, seed=seed)
        out, tp, _, tier2_subject, enc_stats = _run_legacy(
            cfg, model, params, prompts, gen, kw,
            frame_lengths=lens, bucket_frames=bucket_frames)
        if enc_stats is not None:
            print(f"[serve] encoder frames: extent {enc_stats['frames_run']}"
                  f"/{enc_stats['frames_capacity']} "
                  f"({'bucketed' if bucket_frames else 'capacity'}), "
                  f"{enc_stats['true_frames']} true + "
                  f"{enc_stats['padded_frames']} padded rows "
                  f"({enc_stats['padded_bytes']} padded bytes)")

    # prompt tokens are NOT generated tokens: report the two rates
    # separately (a single blended tok/s overstates decode by counting
    # teacher-forced prefill pushes at the same rate)
    print(f"[serve] {arch}: {batch} seqs, prompt {prompt_len} + gen {gen} "
          f"[kv={kv}] | prefill {tp['prefill_tok_s']:.0f} tok/s, "
          f"decode {tp['decode_tok_s']:.0f} tok/s (live slots)")
    if stats is not None:
        print(f"[serve] prefix hits: {stats['prefix_hits']} "
              f"({stats['prefix_hit_tokens']} tokens served from cache), "
              f"computed {stats['prefill_computed_tokens']} of "
              f"{stats['prefill_tokens']} prompt tokens, "
              f"padded waste {stats['padded_prefill_tokens']} tokens, "
              f"pages freed {stats['pages_freed']}")
    if spec and stats is not None:
        mode = "rollback" if (spec_rollback and kv == "paged") \
            else "overwrite"
        print(f"[serve] spec[{draft},{mode}]: accepted drafts: "
              f"{stats['draft_accepted']} of {stats['draft_proposed']} "
              f"proposed (accept rate {tp.get('accept_rate', 0.0):.2f}) | "
              f"draft {tp.get('draft_tok_s', 0.0):.0f} tok/s, "
              f"verify {tp.get('verify_tok_s', 0.0):.0f} tok/s over "
              f"{stats['spec_ticks']} verify ticks")
    print("[serve] sample continuation:", np.asarray(out[0])[:12])

    obj_scan = None
    if obj_registry is not None:
        from repro.core.replicas import ReplicaDetector
        obj_scan = ReplicaDetector(obj_registry).scan()
        print(f"[serve] object scan: {len(obj_registry)} live objects, "
              f"{len(obj_scan.findings)} replica groups, "
              f"{sum(f.bytes for f in obj_scan.findings):.0f} "
              f"duplicate bytes")
        print(obj_scan.render(top_k=5, by="object"))

    if profile:
        # one merged WasteProfile for the serving path (DESIGN.md §2):
        # Tier-3 serve detectors on the live engine, Tier-2 on the
        # compiled decode step + the engine's padding accounting, Tier-1
        # (trace→replay) on a single-token decode microstep
        tier2 = analyze_waste(tier2_subject.compile().as_text()).profile
        pc = ProfilerConfig(enabled=True, period=5000, seed=seed)
        cache1 = model.init_cache(params, batch, prompt_len + gen + 1,
                                  kv_dtype=jnp.float32, **kw)
        dparams = model.decode_params(params)
        tok1 = out[:, -1:]
        tier1 = profile_fn(
            lambda tok: make_serve_step(model)(dparams, cache1, tok)[0],
            tok1, cfg=pc, epochs=2)
        profs = [tier1, tier2] + ([tier3] if tier3 is not None else [])
        if stats is not None:
            profs.append(padding_waste_profile(stats))
        if enc_stats is not None:
            profs.append(encoder_padding_profile(enc_stats))
        if obj_scan is not None:
            profs.append(obj_scan)
        merged = merge_profiles(profs)
        print(merged.render(top_k=3))
        if profile_out:
            dump_json(merged, profile_out)
            print(f"[serve] waste profile written to {profile_out}")
        if sarif_out:
            write_sarif(merged, sarif_out, src_root=os.getcwd())
            print(f"[serve] SARIF findings written to {sarif_out}")
    else:
        merged = None
    # same contract as launch.train.run: (result, merged profile or None)
    return out, merged


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kv", default="dense", choices=("dense", "paged"))
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--spec", default="off", choices=("on", "off"),
                    help="speculative decoding (draft + width-k verify)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify window")
    ap.add_argument("--draft", default="ngram",
                    choices=("ngram", "oracle", "lm"),
                    help="drafter: self-speculative n-gram lookup, the "
                         "replay oracle (runs a plain pass first; "
                         "accept-rate 1.0), or the model drafting for "
                         "itself")
    ap.add_argument("--spec-rollback", default="on", choices=("on", "off"),
                    help="paged only: roll the commit back to the accept "
                         "point instead of storing rejected draft rows")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--profile-out", default=None)
    ap.add_argument("--sarif-out", default=None,
                    help="write the merged waste profile as SARIF 2.1.0")
    ap.add_argument("--objects", action="store_true",
                    help="register params + KV pages in the object "
                         "registry and run the replica scan")
    ap.add_argument("--bucket-frames", default="on", choices=("on", "off"),
                    help="audio family: run the encoder at the "
                         "power-of-two bucket of the batch's longest "
                         "true frame length instead of always padding "
                         "to cfg.encoder_frames (outputs identical; "
                         "prefill_padding bytes drop)")
    a = ap.parse_args()
    enable_compile_cache()
    run(a.arch, smoke=a.smoke, batch=a.batch, prompt_len=a.prompt_len,
        gen=a.gen, profile=a.profile, profile_out=a.profile_out,
        sarif_out=a.sarif_out,
        kv=a.kv, page_size=a.page_size, spec=a.spec == "on",
        spec_k=a.spec_k, draft=a.draft,
        spec_rollback=a.spec_rollback == "on", objects=a.objects,
        bucket_frames=a.bucket_frames == "on")


if __name__ == "__main__":
    main()

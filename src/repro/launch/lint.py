"""Static waste lint driver: tier-0 jaxpr analysis over the model zoo.

Traces the train step, decode step / engine tick, and prefill of each
config in ``configs/registry.py`` ABSTRACTLY (ShapeDtypeStruct in,
jaxpr out — no parameter allocation, no compile, no device) and runs
``core/jaxpr_lint.py`` over the closed jaxprs. Findings merge into one
tier-0 :class:`WasteProfile` and export as SARIF for CI annotation.

Baseline workflow (CI ``lint-zoo`` job):

    # fail only on NEW findings vs the committed waiver baseline
    python -m repro.launch.lint --all-configs \
        --baseline lint_baseline.json --sarif-out lint.sarif

    # intentionally accept the current findings (reviewed!)
    python -m repro.launch.lint --all-configs \
        --baseline lint_baseline.json --update-baseline

A waiver entry records the finding's stable fingerprint (sha over the
§5.6 key kind|tier|C1|C2 — contexts use file BASENAMES, so baselines
are machine-portable) plus human-readable provenance and a note field
for the review rationale.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.core.findings import WasteProfile, merge_profiles
from repro.core.jaxpr_lint import lint_fn
from repro.core.report import dump_json
from repro.core.sarif import finding_fingerprint, write_sarif
from repro.models.zoo import build_model
from repro.serve.decode import (make_engine_prefill, make_engine_tick,
                                make_serve_step)
from repro.serve.engine import ENGINE_FAMILIES
from repro.train import state as TS
from repro.train.step import make_train_step
from repro.runtime.compile_cache import enable_compile_cache

BASELINE_VERSION = 1


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _train_batch(cfg, batch: int, seq: int) -> Dict[str, Any]:
    """Abstract batch matching data/synthetic.batch_at's leaves."""
    out = {"tokens": _sds((batch, seq), jnp.int32),
           "labels": _sds((batch, seq), jnp.int32)}
    if cfg.family == "vlm":
        out["img"] = _sds((batch, cfg.num_image_tokens, cfg.d_model),
                          jnp.float32)
    if cfg.family == "audio":
        out["frames"] = _sds((batch, min(seq, cfg.encoder_frames),
                              cfg.d_model), jnp.float32)
    return out


def _abstract_cache(model, params, batch: int, max_len: int):
    """Decode cache shapes without allocating (init_cache under
    eval_shape; cross-KV families get abstract img/frames)."""
    cfg = model.cfg
    kw: Dict[str, Any] = {}
    if cfg.family == "vlm":
        kw["img"] = _sds((batch, cfg.num_image_tokens, cfg.d_model),
                         jnp.float32)
    if cfg.family == "audio":
        kw["frames"] = _sds((batch, cfg.encoder_frames, cfg.d_model),
                            jnp.float32)
    fn = lambda p, kw2: model.init_cache(p, batch, max_len, **kw2)
    return jax.eval_shape(fn, params, kw)


def lint_config(arch: str, *, smoke: bool = True, batch: int = 2,
                seq: int = 32, max_len: int = 48,
                subjects: Tuple[str, ...] = ("train", "decode", "prefill"),
                verbose: bool = False) -> List[WasteProfile]:
    """Lint one zoo config's step functions; one profile per subject."""
    cfg = registry.get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    profiles: List[WasteProfile] = []

    def note(msg):
        if verbose:
            print(f"[lint]   {msg}", flush=True)

    if "train" in subjects:
        tc = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=1)
        step_fn = make_train_step(model, tc, None)
        state = TS.abstract(model)
        profiles.append(lint_fn(step_fn, state, _train_batch(cfg, batch, seq),
                                subject=f"{arch}:train_step"))
        note(f"train_step: {len(profiles[-1].findings)} findings")

    params = model.abstract_params()
    # decode subjects get the decode-path param view: encoder/cross-KV
    # leaves only feed init_cache, and as decode invars they'd lint as
    # dead_param (they ARE dead there — the fix is to not pass them)
    dparams = model.decode_params(params)
    engine = cfg.family in ENGINE_FAMILIES

    if "decode" in subjects:
        cache = _abstract_cache(model, params, batch, max_len)
        if engine:
            tick = make_engine_tick(model)
            prof = lint_fn(tick, dparams, cache,
                           _sds((batch, 1), jnp.int32),
                           _sds((batch,), jnp.bool_),
                           subject=f"{arch}:engine_tick")
        else:
            step = make_serve_step(model)
            prof = lint_fn(step, dparams, cache,
                           _sds((batch, 1), jnp.int32),
                           subject=f"{arch}:decode_step")
        profiles.append(prof)
        note(f"decode: {len(prof.findings)} findings")

    if "prefill" in subjects:
        P = min(16, max_len - 1)
        cache = _abstract_cache(model, params, batch, max_len)
        if engine:
            pf = make_engine_prefill(model)
            prof = lint_fn(pf, dparams, cache,
                           _sds((batch, P), jnp.int32),
                           _sds((batch,), jnp.bool_),
                           _sds((batch,), jnp.int32),
                           _sds((batch,), jnp.int32),
                           _sds((batch, 1), jnp.int32),
                           subject=f"{arch}:engine_prefill")
        else:
            fn = lambda p, c, t: model.prefill(p, c, t)
            prof = lint_fn(fn, dparams, cache, _sds((batch, P), jnp.int32),
                           subject=f"{arch}:prefill")
        profiles.append(prof)
        note(f"prefill: {len(prof.findings)} findings")
    return profiles


# ---------------------------------------------------------------------
# waiver baseline
# ---------------------------------------------------------------------
def load_baseline(path: str) -> Dict[str, Dict[str, Any]]:
    """fingerprint -> waiver entry. Missing file = empty baseline."""
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        doc = json.load(fh)
    return {w["fingerprint"]: w for w in doc.get("waivers", [])}


def baseline_doc(profile: WasteProfile) -> Dict[str, Any]:
    waivers = []
    for f in sorted(profile.findings,
                    key=lambda f: (f.kind, f.tier, f.c1, f.c2)):
        waivers.append({
            "fingerprint": finding_fingerprint(f),
            "kind": f.kind,
            "tier": f.tier,
            "subject": f.meta.get("subject", ""),
            "c1": list(f.c1),
            "c2": list(f.c2),
            "bytes": f.bytes,
            "note": f.meta.get("rule", ""),
        })
    return {"version": BASELINE_VERSION, "waivers": waivers}


def split_new(profile: WasteProfile, waived: Dict[str, Dict[str, Any]]):
    """Partition findings into (new, waived-hit) by stable fingerprint."""
    new, hit = [], []
    for f in profile.findings:
        (hit if finding_fingerprint(f) in waived else new).append(f)
    return new, hit


# ---------------------------------------------------------------------
def run(archs: List[str], *, smoke: bool = True,
        subjects: Tuple[str, ...] = ("train", "decode", "prefill"),
        sarif_out: Optional[str] = None,
        profile_out: Optional[str] = None,
        baseline: Optional[str] = None,
        update_baseline: bool = False,
        verbose: bool = False) -> Tuple[WasteProfile, int]:
    """Lint archs; returns (merged tier-0 profile, exit code)."""
    profiles: List[WasteProfile] = []
    for arch in archs:
        print(f"[lint] {arch} ...", flush=True)
        try:
            profiles.extend(lint_config(arch, smoke=smoke,
                                        subjects=subjects, verbose=verbose))
        except Exception as e:                      # pragma: no cover
            print(f"[lint] {arch} FAILED to trace: {e!r}", file=sys.stderr)
            raise
    merged = merge_profiles(profiles)
    merged.meta.setdefault("subjects", ",".join(subjects))

    print(f"[lint] {len(archs)} configs, {len(merged.findings)} findings, "
          f"fractions {merged.fractions()}")
    for f in merged.top(20):
        where = (f"{os.path.basename(str(f.meta.get('file', '?')))}:"
                 f"{f.meta.get('line', 0)}" if "file" in f.meta
                 else f.meta.get("path", "-"))
        print(f"  {f.kind:16s} {f.bytes / 1e3:10.1f} KB x{f.count:<4d} "
              f"{f.meta.get('subject', '?'):40s} {where}")

    if sarif_out:
        root = os.getcwd()
        write_sarif(merged, sarif_out, src_root=root)
        print(f"[lint] SARIF written to {sarif_out}")
    if profile_out:
        dump_json(merged, profile_out)
        print(f"[lint] waste profile written to {profile_out}")

    code = 0
    if baseline and update_baseline:
        with open(baseline, "w") as fh:
            json.dump(baseline_doc(merged), fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"[lint] baseline updated: {baseline} "
              f"({len(merged.findings)} waivers)")
    elif baseline:
        waived = load_baseline(baseline)
        new, hit = split_new(merged, waived)
        print(f"[lint] baseline {baseline}: {len(hit)} waived, "
              f"{len(new)} NEW")
        if new:
            print("[lint] new findings (fail):")
            for f in sorted(new, key=lambda f: -f.bytes):
                print(f"  {finding_fingerprint(f)[:12]} {f.kind:16s} "
                      f"{f.meta.get('subject', '?')} :: "
                      f"{f.meta.get('rule', f.meta.get('path', ''))}")
            code = 1
    return merged, code


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Tier-0 static jaxpr waste lint over the model zoo")
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--config", choices=registry.ARCH_IDS,
                   help="lint one zoo config")
    g.add_argument("--all-configs", action="store_true",
                   help="lint every config in the registry")
    ap.add_argument("--full-size", action="store_true",
                    help="lint at full config size (default: .smoke())")
    ap.add_argument("--subjects", default="train,decode,prefill",
                    help="comma list from {train,decode,prefill}")
    ap.add_argument("--sarif-out", default=None,
                    help="write findings as SARIF 2.1.0")
    ap.add_argument("--profile-out", default=None,
                    help="write the tier-0 WasteProfile as JSON")
    ap.add_argument("--baseline", default=None,
                    help="waiver baseline JSON; NEW findings exit 1")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite --baseline from current findings")
    ap.add_argument("-v", "--verbose", action="store_true")
    a = ap.parse_args(argv)
    enable_compile_cache()
    archs = registry.ARCH_IDS if a.all_configs else [a.config]
    subjects = tuple(s for s in a.subjects.split(",") if s)
    _, code = run(archs, smoke=not a.full_size, subjects=subjects,
                  sarif_out=a.sarif_out, profile_out=a.profile_out,
                  baseline=a.baseline, update_baseline=a.update_baseline,
                  verbose=a.verbose)
    return code


if __name__ == "__main__":
    sys.exit(main())

"""End-to-end training driver.

Integrates every substrate: config registry -> model zoo -> synthetic data
(+prefetch) -> pjit'd mixed-precision train step -> checkpointing (atomic,
async) -> fault monitor -> JXPerf-JAX Tier-3 detectors (--profile) and a
Tier-2 HLO waste report of the compiled step (--waste-report).

CPU smoke:  PYTHONPATH=src python -m repro.launch.train \
                --arch qwen3-1.7b --smoke --steps 20
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import registry
from repro.configs.base import ProfilerConfig, TrainConfig
from repro.core.detectors import TrainingDetectors
from repro.core.findings import merge_profiles
from repro.core.hlo_waste import analyze_waste
from repro.core.objects import ObjectRegistry
from repro.core.replicas import ReplicaDetector
from repro.core.report import dump_json
from repro.core.sarif import write_sarif
from repro.data.pipeline import Prefetcher
from repro.data.synthetic import stream
from repro.launch.mesh import make_host_mesh
from repro.models.zoo import build_model
from repro.runtime.fault import FleetMonitor
from repro.sharding.rules import make_strategy
from repro.train import state as TS
from repro.train.step import make_train_step
from repro.runtime.compile_cache import enable_compile_cache


def jit_train_step(model, tc: TrainConfig, strategy=None, *,
                   donate: bool = True):
    """The jitted train step and the host-batch -> device placement that
    goes with it. Under a sharding ``strategy`` the state keeps its
    `TS.state_shardings` across steps and the batch is split over the
    strategy's batch axes; without one everything sits on the default
    device."""
    step_fn = make_train_step(model, tc, strategy)
    donate_argnums = (0,) if donate else ()
    if strategy is None:
        return (jax.jit(step_fn, donate_argnums=donate_argnums),
                lambda b: {k: jnp.asarray(v) for k, v in b.items()})
    jit_step = jax.jit(step_fn, donate_argnums=donate_argnums,
                       out_shardings=(TS.state_shardings(model, strategy),
                                      None))
    batch_sharding = strategy.named(
        jax.sharding.PartitionSpec(strategy.batch_axes))

    def to_device(b):
        return {k: jax.device_put(jnp.asarray(v), batch_sharding)
                for k, v in b.items()}
    return jit_step, to_device


def run(arch: str, *, smoke: bool = True, steps: int = 50, batch: int = 8,
        seq: int = 128, lr: float = 3e-4, ckpt_dir: str = None,
        ckpt_every: int = 25, profile: bool = False,
        waste_report: bool = False, resume: bool = False,
        microbatches: int = 1, remat: str = "none", seed: int = 0,
        log_every: int = 10, strategy: str = None, total_steps: int = None,
        profile_out: str = None, sarif_out: str = None,
        objects: bool = False):
    cfg = registry.get_config(arch)
    if smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    # total_steps fixes the LR schedule horizon independently of how many
    # steps this invocation runs (checkpoint/restart determinism)
    horizon = total_steps or steps
    tc = TrainConfig(learning_rate=lr, total_steps=horizon,
                     warmup_steps=max(horizon // 10, 1),
                     microbatches=microbatches, remat=remat, seed=seed)

    strat = make_strategy(strategy, make_host_mesh()) if strategy else None
    # Tier-3 detectors hold pre-step params across the call -> no donation
    jit_step, to_device = jit_train_step(model, tc, strat,
                                         donate=not profile)

    obj_registry = ObjectRegistry() if objects else None
    state = TS.create(model, jax.random.PRNGKey(seed),
                      registry=obj_registry, strategy=strat)
    obj_scan = None
    if obj_registry is not None:
        # scan AT INIT: the moments are all bit-identical zeros here —
        # the replica_opt_state lazy-materialize finding in its purest
        # form (post-training they diverge and the story is gone)
        obj_scan = ReplicaDetector(obj_registry).scan()
        print(f"[train] object scan: {len(obj_registry)} live objects, "
              f"{len(obj_scan.findings)} replica groups, "
              f"{sum(f.bytes for f in obj_scan.findings):.0f} "
              f"duplicate bytes")
    start_step = 0
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ckpt and resume and ckpt.latest_step() is not None:
        state = ckpt.restore(TS.abstract(model))
        start_step = int(state.step)
        print(f"[train] resumed from step {start_step}")

    detectors = TrainingDetectors(ProfilerConfig(enabled=True)) if profile else None
    monitor = FleetMonitor(hosts=[0], dead_after=3600.0)

    data = Prefetcher(stream(cfg, batch, seq, seed=seed, start_step=start_step))

    tier2_profile = None
    if waste_report:
        b0 = next(iter(data))
        lowered = jit_step.lower(state, to_device(b0))
        rep = analyze_waste(lowered.compile().as_text())
        print(rep.summary())
        tier2_profile = rep.profile

    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        b = next(data)
        batch_dev = to_device(b)
        if detectors:
            detectors.on_batch(step, b)
            params_before = state.params
        t0 = time.time()
        state, metrics = jit_step(state, batch_dev)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor.heartbeat(0, time.time() - t0)
        if detectors:
            detectors.on_step(step, params_before, state.params)
        if ckpt and (step + 1) % ckpt_every == 0:
            ckpt.save_async(step + 1, state)
        if (step + 1) % log_every == 0 or step == start_step:
            print(f"[train] step {step+1:5d} loss {loss:8.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        plan = monitor.plan()
        if plan["action"] == "abort":
            raise RuntimeError(plan["reason"])
    if ckpt:
        ckpt.save(steps, state)
        ckpt.wait()
    data.close()
    dt = time.time() - t_start
    print(f"[train] done: {steps - start_step} steps in {dt:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    # one merged WasteProfile across tiers (DESIGN.md §2): Tier-3 step
    # findings + Tier-2 compiled-step findings coalesce into one report
    parts = [p for p in (detectors.report if detectors else None,
                         tier2_profile, obj_scan) if p is not None]
    profile_merged = merge_profiles(parts) if parts else None
    if profile_merged is not None:
        print(profile_merged.render(top_k=5))
        if profile_out:
            dump_json(profile_merged, profile_out)
            print(f"[train] waste profile written to {profile_out}")
        if sarif_out:
            write_sarif(profile_merged, sarif_out, src_root=os.getcwd())
            print(f"[train] SARIF findings written to {sarif_out}")
    return losses, profile_merged


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--waste-report", action="store_true")
    ap.add_argument("--objects", action="store_true",
                    help="register params/opt state in the object "
                         "registry and run the replica scan at init")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-out", default=None,
                    help="write the merged waste profile as JSON")
    ap.add_argument("--sarif-out", default=None,
                    help="write the merged waste profile as SARIF 2.1.0")
    a = ap.parse_args()
    enable_compile_cache()
    run(a.arch, smoke=a.smoke, steps=a.steps, batch=a.batch, seq=a.seq,
        lr=a.lr, ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every,
        profile=a.profile, waste_report=a.waste_report, resume=a.resume,
        microbatches=a.microbatches, remat=a.remat, seed=a.seed,
        profile_out=a.profile_out, sarif_out=a.sarif_out,
        objects=a.objects)


if __name__ == "__main__":
    main()

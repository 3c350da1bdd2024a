"""A training cell: a closed loop of the program's compiled train step.

Set-up builds one object, the jitted step from ``launch/train.
jit_train_step`` with its state (the benchmark's weights as the f32
master, the program's own compute dtype and AdamW state), and drives it
through its first ``warm_steps`` steps on batches whose rows all differ.
Those steps compile it and give the readings that ``correct`` compares:
each step's loss, the first clipped gradient (from the first moment
after one step) and the change of the master weights after the steps.
The window then runs the same object on, one step after another, on
``distinct_batches`` further batches in turn, reading each step's loss
as a training loop does.
"""
from __future__ import annotations

import gc
import inspect
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, traffic, weights
from benchmarks.chip.common import GCClock, TracedSpan, log, model_config


def train_config(cell):
    from repro.configs.base import TrainConfig
    return TrainConfig(**cell.config["train"])


def build(cell, seed: int):
    """(model, jitted step, host->device feed, initial state)."""
    from repro.launch.train import jit_train_step
    from repro.models.zoo import build_model
    from repro.optim import adamw
    from repro.train import state as TS
    model = build_model(model_config(cell.config))
    step, to_device = jit_train_step(model, train_config(cell))
    compute = inspect.signature(TS.create).parameters["compute_dtype"].default
    abstract = model.abstract_params(jnp.float32)
    make = weights.param_builder(abstract, model.cfg.d_model)

    @jax.jit
    def init(key):
        master = make(key)
        return TS.TrainState(
            params=jax.tree_util.tree_map(lambda p: p.astype(compute),
                                          master),
            master=master, opt=adamw.init(master),
            step=jnp.zeros((), jnp.int32))
    return model, step, to_device, init(weights.key(seed)), make


def batches(cell, seed: int, first: int, n: int) -> List[Dict]:
    job = cell.traffic
    V = cell.config["model"]["vocab_size"]
    return [traffic.synthetic_lm(V, job["batch"], job["seq"], seed, s)
            for s in range(first, first + n)]


def run(cell, seed: int, seconds: float, trace_dir: Optional[str],
        clock, since_start, fault=None) -> Dict[str, Any]:
    """One run. ``fault(step) -> step`` plants a fault (tests only)."""
    job = cell.traffic
    model, step, to_device, state, make = build(cell, seed)
    log(f"[setup] weights and train state at {since_start()!r} s")
    if fault is not None:
        step = fault(step)
    b1 = cell.config["train"]["b1"]
    warm = batches(cell, seed, 0, job["warm_steps"])
    losses, g_first = [], None
    for b in warm:
        state, met = step(state, to_device(b))
        losses.append(float(met["loss"]))
        if g_first is None:
            g_first = check.leaf_norms(state.opt.m) / (1.0 - b1)
    key = weights.key(seed)
    change = np.asarray(jax.jit(lambda m, k: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(m),
            jax.tree_util.tree_leaves(make(k)))]))(state.master, key))
    feed = [to_device(b) for b in batches(cell, seed, job["warm_steps"],
                                           job["distinct_batches"])]
    jax.block_until_ready(feed)
    log(f"[setup] {job['warm_steps']} warm steps and the feed at "
        f"{since_start()!r} s")
    tokens = job["batch"] * job["seq"]
    compiles0 = clock.count
    setup_s = since_start()

    traced = TracedSpan(trace_dir, seconds)
    spans: List[tuple] = []
    jax.config.update("jax_log_compiles", True)   # name any in the window
    gcs = GCClock().install()
    t0 = time.perf_counter()
    now = lambda: time.perf_counter() - t0
    k = 0
    while now() < seconds:
        traced.poll(now(), now)
        ts = now()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, met = step(state, feed[k % len(feed)])
            float(met["loss"])
        spans.append((ts, now()))
        k += 1
    jax.config.update("jax_log_compiles", False)
    traced.close(now)
    win = spans[-1][1]
    compiles = clock.count - compiles0
    gcs.remove()
    dt = np.asarray([e - s for s, e in spans])
    med = float(np.median(dt))
    slow = np.flatnonzero(dt > 2 * med)
    log(f"[train] window {win:.3f} s, steps {len(spans)}, step time median "
        f"{1e3 * med:.3f} ms, compiles in window {compiles}")
    log(f"[train] steps over twice the median: {slow.tolist()} losing "
        f"{float(np.sum(dt[slow] - med))!r} s; in the window "
        + gcs.summary(t0))
    result = {
        "setup_s": setup_s, "attempted": len(spans), "failed": 0,
        "metrics": {"train_tokens_per_s": len(spans) * tokens / win},
        "counters": {"steps": len(spans), "tokens_per_step": tokens,
                     "seq": job["seq"], "compiles_in_window": compiles,
                     "trace_host_s": traced.host_s,
                     "steps_in_trace": sum(traced.covers(a, b)
                                           for a, b in spans)},
        "model": cell.config["model"],
        "readings": (np.asarray(losses), g_first, change),
    }
    del state, feed, step
    gc.collect()
    return result


def correctness(cell, seed: int, result) -> Dict[str, Dict[str, float]]:
    from repro.models.zoo import build_model
    model = build_model(model_config(cell.config))
    params = weights.make_params(model.abstract_params(jnp.float32), seed,
                                 model.cfg.d_model)
    refr = check.reference_train(
        params, cell.config["model"], cell.config["train"],
        batches(cell, seed, 0, cell.traffic["warm_steps"]))
    nums = check.train_numbers(result["readings"], refr)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    for k, i in check.worst_leaves(result["readings"], refr).items():
        log(f"[train] {k} is set by leaf {paths[i]}")
    lim = cell.config["check"]
    for k in sorted(set(nums) - set(lim)):      # read, with no limit
        log(f"[train] {k} {nums[k]!r} (not compared)")
    return {k: {"value": nums[k], "limit": v} for k, v in lim.items()}

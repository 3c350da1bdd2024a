#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's compared
numbers over many seeds (the lower reading) and the precision control's
(the upper reading), in one process. Not part of a benchmark run.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds 11,12,13 --control-seeds 11,12,13 [--seconds 15] \\
        [--fault half_batch]

Serving: each seed runs the timed path for ``--seconds`` at the cell's
own load, then reads the widest gap of the served tokens and, for the
control seeds, of the tokens the float8 reference ranks first at the
same positions. Training: each seed runs the first steps and reads the
three numbers; the control is the program with its own int8 gradient
compression switched on (``grad_compression: "int8_ef"``), against the
same reference; ``--fault half_batch`` runs the program on the first
half of each batch. One JSON line per seed.
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def serve_readings(cell, seed: int, seconds: float, control: bool):
    from benchmarks.chip import check, serve_cell, weights
    from benchmarks.chip.common import CompileClock, model_config
    from repro.models.zoo import build_model
    t0 = time.perf_counter()
    res = serve_cell.run(cell, seed, seconds, None, CompileClock(),
                         lambda: time.perf_counter() - t0)
    model = build_model(model_config(cell.config))
    params = weights.make_params(model.abstract_params(), seed,
                                 cell.config["model"]["d_model"])
    m, L = cell.config["model"], cell.config["engine"]["max_len"]
    seqs = res["check_inputs"]
    if control:
        gap, ctl = check.serve_gap_and_control(params, m, seqs, L)
    else:
        gap, ctl = check.serve_gap(params, m, seqs, L), None
    return {"seed": seed, "max_logit_gap": gap, "control": ctl,
            "served_tokens": sum(len(g) for _, g in seqs),
            "failed": res["failed"], "metrics": res["metrics"]}


def half_batch(step):
    def bad(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})
    return bad


def int8_gradients(cell):
    """The cell with the program's int8 gradient compression on."""
    ctl = copy.deepcopy(cell)
    ctl.config["train"]["grad_compression"] = "int8_ef"
    return ctl


def train_readings(cell, seed: int, control: bool, fault=None):
    import jax.numpy as jnp
    from benchmarks.chip import check, train_cell, weights
    from benchmarks.chip.common import CompileClock, model_config
    from repro.models.zoo import build_model

    def program(c, fault=None):
        t0 = time.perf_counter()
        return train_cell.run(c, seed, 1.0, None, CompileClock(),
                              lambda: time.perf_counter() - t0,
                              fault=fault)["readings"]
    res = program(cell, fault)
    ctl = program(int8_gradients(cell)) if control else None
    model = build_model(model_config(cell.config))
    abstract = model.abstract_params(jnp.float32)
    fresh = lambda: weights.make_params(abstract, seed, model.cfg.d_model)
    m, tc = cell.config["model"], cell.config["train"]
    bs = train_cell.batches(cell, seed, 0, cell.traffic["warm_steps"])
    refr = check.reference_train(fresh(), m, tc, bs)
    out = {"seed": seed, **check.train_numbers(res, refr)}
    if control:
        out["control"] = check.train_numbers(ctl, refr)
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=("half_batch",), default=None)
    a = ap.parse_args(argv)
    from benchmarks.chip import spec
    from benchmarks.chip.run import require_chips
    from repro.runtime.compile_cache import enable_compile_cache
    cell = spec.resolve(a.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",") if s]
    ctl = {int(s) for s in a.control_seeds.split(",") if s}
    for seed in seeds:
        if cell.kind == "sessions":
            r = serve_readings(cell, seed, a.seconds, seed in ctl)
        else:
            r = train_readings(cell, seed, seed in ctl,
                               half_batch if a.fault else None)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    # run as a script: import the benchmark as a package, and the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())

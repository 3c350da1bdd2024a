#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (process start to the window): load the cell by name, make the
weights on the device from the seed, turn on the compile cache at the
program's fixed path, warm every shape the cell uses. Then measure for
``--seconds``, check what the timed path produced against the plain
reference, and print one JSON line last on standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (from a profiler trace of part of the window, and the
run's counters) and a ``breakdown``. The numbers compared for
``correct`` come last, beside their limits. A machine without the chips
the cell asks for exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise SystemExit(f"run.py: this cell needs {n} TPU chip(s); JAX "
                         f"found {len(devs)} {devs[0].platform} device(s)")


def per_layer(cell, result, trace_dir, device):
    """The cell's per-layer metrics from the traced window's reduction
    and the run's counters. A reader that finds nothing returns None and
    its metric is left out."""
    import glob
    from benchmarks.chip import flops, trace
    from benchmarks.chip.common import log
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    red = trace.reduce(files[0], kernels=("paged_decode_attention",
                                          "paged_window_attention")) \
        if files else None
    if red is None or red.busy_s <= 0:
        raise SystemExit("run.py: the trace holds no device operation")
    result["reduction"] = red
    result["peaks"] = flops.peak(device["kind"])
    metrics = {}
    for m in cell.per_layer:
        v = m.read(result)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    log(f"[trace] window {red.window_s!r} s, busy {red.busy_s!r} s over "
        f"{red.devices} device(s); kernel calls "
        + ", ".join(f"{k} {len(v)}" for k, v in red.kernels.items()))
    return metrics, red


def main(argv=None) -> int:
    a = parse(argv)
    from benchmarks.chip import spec
    cell = spec.resolve(a.workload)
    require_chips(cell.chips)
    import jax
    from benchmarks.chip.common import log
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"[run] compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    execute(cell, a.seed, a.seconds, bool(a.trace))
    return 0


def execute(cell, seed: int, seconds: float, trace: bool, fault=None,
            since=None) -> dict:
    """Set up, measure, check and print one run of ``cell``; returns the
    result line. ``fault`` plants a fault in the timed path (tests)."""
    import jax
    from benchmarks.chip import common
    clock = common.CompileClock().install()
    common.log(f"[run] {cell.name} seed {seed} on "
               f"{len(jax.devices())} x {jax.devices()[0].device_kind}")
    driver = _driver(cell)
    trace_dir = None
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="chip_trace_")
    since = since or (lambda: time.perf_counter() - T_START)
    result = driver.run(cell, seed, seconds, trace_dir, clock, since,
                        fault=fault)
    device = common.device_info()
    breakdown = None
    if trace:
        import shutil
        metrics, red = per_layer(cell, result, trace_dir, device)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m.name: {"value": (result["setup_s"] if m.name == "setup_s"
                                      else result["metrics"][m.name]),
                            "unit": m.unit}
                   for m in cell.end_to_end}
    common.log(f"[run] setup_s {result['setup_s']!r}; compile seconds "
               f"{clock.secs!r} over {clock.count} programs")
    checks = driver.correctness(cell, seed, result)
    correct = (result["failed"] == 0
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values())
               and all(v["value"] is not None for v in metrics.values()))
    return common.emit(correct=correct, attempted=result["attempted"],
                       failed=result["failed"], metrics=metrics,
                       device=device, checks=checks, breakdown=breakdown)


def _driver(cell):
    if cell.kind == "sessions":
        from benchmarks.chip import serve_cell
        return serve_cell
    if cell.kind == "synthetic_lm":
        from benchmarks.chip import train_cell
        return train_cell
    raise SystemExit(f"run.py: no driver for traffic kind {cell.kind!r}")


if __name__ == "__main__":
    # run as a script: import the benchmark as a package, and the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())

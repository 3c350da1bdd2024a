#!/usr/bin/env python3
"""Find a serving cell's knee: the same cell at several fixed rates, in
one process. Not part of a benchmark run; its result is written into
the mix's ``rate_per_s`` (4/5 of the knee) and into PERF.md.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,4 \\
        [--seconds 51] [--seeds 7,8]

One JSON line per rate and seed: the end-to-end metrics, the TTFT's
95th percentile beside its 70th, the requests still pending when the
window closed (a growing backlog), and the queue wait.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    import argparse
    import numpy as np
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", default="7")
    a = ap.parse_args(argv)
    from benchmarks.chip import serve_cell, spec
    from benchmarks.chip.common import CompileClock
    from benchmarks.chip.run import require_chips
    from repro.runtime.compile_cache import enable_compile_cache
    cell = spec.resolve(a.workload)
    require_chips(cell.chips)
    enable_compile_cache()
    for rate, seed in [(float(r), int(s)) for r in a.rates.split(",")
                       for s in a.seeds.split(",")]:
        cell.traffic["rate_per_s"] = rate
        t0 = time.perf_counter()
        res = serve_cell.run(cell, seed, a.seconds, None, CompileClock(),
                             lambda: time.perf_counter() - t0)
        c = res["counters"]
        print(json.dumps({
            "rate_per_s": rate, "seed": seed, **res["metrics"],
            "ttft_p95_ms": 1e3 * float(np.percentile(c["ttft_s"], 95))
            if c["ttft_s"] else None,
            "failed": res["failed"], "attempted": res["attempted"],
            "pending_at_close": c["pending_at_close"],
            "queue_wait_p70_ms": 1e3 * float(np.percentile(
                c["queue_wait_s"], 70)) if c["queue_wait_s"] else None,
            "prefix_hit_tokens": c["stats"]["prefix_hit_tokens"],
            "prefill_tokens": c["stats"]["prefill_tokens"]}), flush=True)
    return 0


if __name__ == "__main__":
    # run as a script: import the benchmark as a package, and the program
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())

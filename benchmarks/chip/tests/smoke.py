"""Smoke-size cells for the CPU tests: the shipped cells' files with the
model cut to a few thousand parameters and the traffic to a few
requests, so a whole run fits a test."""
from __future__ import annotations

import copy

from benchmarks.chip import spec

SMOKE_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
               "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
               "vocab_size": 256}


def cell(name: str, **traffic) -> spec.Cell:
    c = copy.deepcopy(spec.resolve(name))
    c.config["model"].update(SMOKE_MODEL)
    if "engine" in c.config:
        c.config["engine"].update(num_slots=4, max_len=160, num_pages=48)
        c.traffic.update(rate_per_s=4.0, system_len=32,
                         user_len={"median": 24, "sigma": 0.5, "min": 8,
                                   "max": 48},
                         unique_len={"median": 32, "sigma": 0.5, "min": 8,
                                     "max": 80},
                         output_len={"median": 12, "sigma": 0.5, "min": 4,
                                     "max": 48})
        c.config["check"]["sample"] = 3
    else:
        c.traffic.update(batch=4, seq=64, distinct_batches=2)
    c.traffic.update(traffic)
    return c


class NullClock:
    count = 0
    secs = 0.0

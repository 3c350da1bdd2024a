"""The precision control fails where the program passes (smoke size).

Serving's control is the plain reference computed on float8 operands
(the step below the configuration's bfloat16), put in the program's
place. Training's is the program's own lower-precision path: its int8
gradient compression switched on. At smoke size on the CPU a control
has to read at least three times what the program reads, on every seed,
for some compared number: that is what lets a limit sit between them.
The chip readings at the cells' own sizes are in PERF.md.
"""

import smoke
from benchmarks.chip import readings

SEEDS = (2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13)


def test_serving_control_reads_three_times_the_program():
    cell = smoke.cell("qwen3-1.7b.chat")
    rs = [readings.serve_readings(cell, s, 1.0, control=True)
          for s in SEEDS]
    assert all(r["failed"] == 0 and r["served_tokens"] > 0 for r in rs)
    assert min(r["control"] for r in rs) >= 3 * max(
        r["max_logit_gap"] for r in rs)


def test_training_control_reads_three_times_the_program():
    cell = smoke.cell("qwen3-1.7b-train1.pretrain")
    rs = [readings.train_readings(cell, s, control=True) for s in SEEDS]
    assert any(min(r["control"][k] for r in rs)
               >= 3 * max(r[k] for r in rs)
               for k in ("loss_gap", "grad_gap", "update_gap"))

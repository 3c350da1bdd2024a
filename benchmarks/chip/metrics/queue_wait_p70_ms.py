"""Scheduler: 70th percentile of the wait from a request's due time to
the start of the engine step that admits it (sets its ``prefill_step``),
over every request admitted in the window before the profiler trace
starts (harness host clock). The percentile is TTFT's: a window holds
some tens of requests, and a 95th would be one of its last two."""
import numpy as np


def read(run):
    waits = run["counters"].get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 70))

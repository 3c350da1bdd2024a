"""Scheduler (``ServeEngine.step``): device idle milliseconds per engine
step, from the device trace. The idle gaps whose middle lies in one of
the engine's ``serve.*`` phase spans are summed and divided by the
``serve.step`` spans inside the traced window (``phases.py``). It holds
the host's own time between device calls and the dispatch latency inside
them. The idle seconds per label, and the engine's spans per step, go
to standard error."""
from benchmarks.chip import phases
from benchmarks.chip.common import log


def read(run):
    red = phases.reduction(run)
    n = phases.steps(red) if red is not None else 0
    if not n:
        return None
    idle = phases.idle_by_label(red)
    spans = sum(1 for s in red.spans if s[0].startswith("serve."))
    log(f"[phases] {n} steps, {spans / n!r} serve.* spans per step; "
        "device idle s by span: "
        + ", ".join(f"{k} {v!r}" for k, v in idle.items()))
    return 1e3 * sum(v for k, v in idle.items()
                     if k.startswith("serve.")) / n

"""Scheduler (``ServeEngine.step``): host milliseconds per engine step
spent outside the device calls, from the engine's own counters over the
window before the profiler trace starts: ``(step_s - prefill_s -
decode_s) / steps``. Every device call blocks, so the device is idle for
all of it."""


def read(run):
    st = run["counters"]["stats_before_trace"]
    if not st.get("steps") or "step_s" not in st:
        return None
    return 1e3 * (st["step_s"] - st["prefill_s"] - st["decode_s"]) \
        / st["steps"]

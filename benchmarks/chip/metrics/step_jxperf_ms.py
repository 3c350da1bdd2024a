"""Profiler (``core/detectors.py``, tiers 3-4): host milliseconds per
engine step spent in calls made for JXPerf (the ``serve.jxperf`` spans:
the detectors' hooks, their watchpoint reads and the kernel-counter
reads), from the engine's ``jxperf_s`` counter over the window before
the profiler trace starts. None without a profiler attached."""


def read(run):
    st = run["counters"]["stats_before_trace"]
    if not st.get("steps") or not st.get("jxperf_s"):
        return None
    return 1e3 * st["jxperf_s"] / st["steps"]

"""Profiler (``core/detectors.py``, tiers 3-4): host milliseconds spent
inside the ``ServingDetectors`` hooks per engine step, timed around the
detectors object the harness attaches, over the window before the
profiler trace starts."""


def read(run):
    c = run["counters"]
    if c.get("hook_s") is None or not c.get("steps_before_trace"):
        return None
    return 1e3 * c["hook_s"] / c["steps_before_trace"]

"""The serving engine's own phases in a traced run.

``ServeEngine.step`` opens a host span ``serve.<phase>`` around each of
its phases (``src/repro/serve/engine.py``), on the clock of the device
ops. ``trace.read_events`` keeps the benchmark's ``bench.*`` spans only;
``read_events`` here keeps both, and ``trace.reduce_events`` then labels
each idle gap with the narrowest span over its middle: inside an engine
step, the phase the host was in.

``run.py`` hands the metrics its reduction of the trace, not the trace's
path. ``reduction`` finds the trace where ``run.py`` writes it (a
``chip_trace_*`` directory under the temporary directory) and takes it
only if it reduces to the same window and busy time as the run's own.
"""
from __future__ import annotations

import glob
import os
import tempfile
from typing import Optional

from benchmarks.chip import trace

SPAN_PREFIXES = ("bench.", "serve.")
STEP_SPAN = "serve.step"


def read_events(path: str):
    """``trace.read_events`` keeping the engine's ``serve.*`` spans too."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            devices[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == trace.OP_LINE
                for e in line.events]
        else:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIXES)]
    return spans, devices


def reduction(run) -> Optional[trace.Reduction]:
    """The run's trace reduced with the engine's spans (kept in ``run``);
    None when no trace that matches ``run["reduction"]`` is found."""
    if "phases" not in run:
        run["phases"] = _find(run["reduction"])
    return run["phases"]


def _find(red: trace.Reduction) -> Optional[trace.Reduction]:
    pattern = os.path.join(tempfile.gettempdir(), "chip_trace_*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        r = trace.reduce_events(*read_events(path))
        if r is not None and (r.window_s, r.busy_s) == (red.window_s,
                                                        red.busy_s):
            return r
    return None


def steps(red: trace.Reduction) -> int:
    """Engine steps wholly inside the traced window."""
    return sum(1 for n, s, e in red.spans
               if n == STEP_SPAN and 0 <= s and e <= red.window_s)


def idle_by_label(red: trace.Reduction):
    """Device idle seconds summed per label of the gaps, largest first."""
    out = {}
    for label, d in red.gaps:
        out[label] = out.get(label, 0.0) + d
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))

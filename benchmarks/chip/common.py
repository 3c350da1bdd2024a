"""What every cell's run shares: the compile clock, the device, the
program's configuration, percentiles and the result line."""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional

import numpy as np


class CompileClock:
    """Seconds and count of backend compiles (cache loads included), from
    JAX's own monitoring events (as ``chip_smoke.py`` reads them)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs, self.count = 0.0, 0

    def __call__(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.secs += duration_secs
            self.count += 1

    def install(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


class GCClock:
    """Python's garbage collections from ``install`` on: (start, seconds,
    generation) each, so a stall on the host can be told from one."""

    def __init__(self):
        self.events: List[tuple] = []
        self._t0 = None

    def __call__(self, phase, info):
        import time
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.events.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))
            self._t0 = None

    def install(self) -> "GCClock":
        import gc
        gc.callbacks.append(self)
        return self

    def remove(self) -> None:
        import gc
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def summary(self, since: float) -> str:
        ev = [e for e in self.events if e[0] >= since]
        if not ev:
            return "no garbage collection"
        return (f"{len(ev)} garbage collections, {sum(e[1] for e in ev)!r} s,"
                f" longest {max(e[1] for e in ev)!r} s (generation "
                f"{max(ev, key=lambda e: e[1])[2]})")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registry's
    architecture with every key of the file's ``model`` applied."""
    from repro.configs import registry
    base = registry.get_config(cfg["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    unknown = set(cfg["model"]) - fields
    if unknown:
        raise KeyError(f"{cfg['name']}: not ModelConfig fields: {unknown}")
    return dataclasses.replace(base, **cfg["model"])


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def peak_bytes(stats: Dict[str, int]) -> int:
    """A chip's peak from its ``memory_stats()``: the peak of the buffers
    the runtime holds (``peak_bytes_in_use``: weights, state, caches,
    a program's outputs) plus the peak of the region it reserves for the
    programs' temporaries (``peak_bytes_reserved`` on a TPU), which the
    first leaves out."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    log(f"[device] memory_stats of the fullest chip: "
        f"{max(stats, key=peak_bytes)}")
    peaks = [peak_bytes(m) for m in stats]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def emit(*, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Any], device: Dict[str, Any],
         checks: Dict[str, Dict[str, float]],
         breakdown: Optional[Dict[str, List]] = None) -> Dict[str, Any]:
    """The result: the compared numbers beside their limits, last on
    standard error; then one JSON line, last on standard output."""
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return line


def start_trace(trace_dir: str) -> None:
    """Start JAX's profiler with Python-function tracing off and only
    the critical host events (the benchmark's own spans among them): the
    rest would slow the host loop it measures. Device ops stay."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


class TracedSpan:
    """The profiler over the last ``length`` seconds of the window.

    It starts that long before the window closes and stops once it has
    closed, so the pause of stopping it (seconds, to gather and write the
    trace) falls after the window. The host span ``bench.traced`` marks
    what it covers for ``trace.py``. Without a ``trace_dir`` it does
    nothing."""

    def __init__(self, trace_dir: Optional[str], seconds: float,
                 length: float = 8.0):
        self.dir = trace_dir
        self.at = seconds - min(length, 0.5 * seconds)
        self.until = seconds
        self.start = self.stop = None

    def poll(self, t: float, now) -> bool:
        """Call between steps at window time ``t``; True when it has
        just started."""
        import jax
        if self.dir is None:
            return False
        if self.start is None and t >= self.at:
            start_trace(self.dir)
            self.start = now()
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            return True
        if t >= self.until:
            self.close(now)
        return False

    def close(self, now) -> None:
        import jax
        if self.start is not None and self.stop is None:
            self._span.__exit__(None, None, None)
            self.stop = now()
            jax.profiler.stop_trace()

    def covers(self, t0: float, t1: float) -> bool:
        return (self.stop is not None and self.start <= t0
                and t1 <= self.stop)

    @property
    def host_s(self) -> Optional[float]:
        return None if self.stop is None else self.stop - self.start

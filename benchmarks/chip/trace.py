"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

From the device planes (``/device:TPU:<n>``) it takes the op line
(``XLA Ops``): busy time is the union of the op intervals inside the
traced window, averaged over the devices that ran anything; idle is the
rest of the window. An op's event is named by its HLO text
(``%paged_decode_attention.1 = (bf16[...]...) custom-call(...)``): the
instruction name keeps the kernel's name with a ``.<n>`` suffix, so time
per kernel is the sum of the durations of the ops whose instruction
name, suffix dropped, is the kernel's. Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies: they count in
busy time but not in the per-op totals. Each idle gap is labelled with
the benchmark's own host span (``bench.*``, written with
``jax.profiler.TraceAnnotation``) that covers its middle, or
``host:none``.

The window is the host span ``bench.traced`` when the trace has one and
device ops fall inside it, else the extent of the device ops.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
_SUFFIX = re.compile(r"\.\d+$")
_CONTAINERS = ("while", "conditional", "call")


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    devices: int
    ops: Dict[str, float] = field(default_factory=dict)   # by instruction
    kernels: Dict[str, List[Tuple[float, float]]] = field(
        default_factory=dict)                   # base name -> (start_s, dur_s)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, name: str) -> float:
        return sum(d for _, d in self.kernels.get(name, []))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def op_label(name: str) -> str:
    """``%fusion.13 = f32[256,512]{...} fusion(...)`` -> ``fusion.13 =
    f32[256,512]``: the instruction and its result's shape."""
    return name.split("{")[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """The instruction name with its ``.<n>`` suffix dropped."""
    return _SUFFIX.sub("", name.split(" = ")[0].strip().lstrip("%"))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(mid: float, spans) -> str:
    best = None
    for name, s, e in spans:
        if s <= mid <= e and name != WINDOW_SPAN:
            if best is None or (e - s) < (best[2] - best[1]):
                best = (name, s, e)
    return best[0] if best else "host:none"


def read_events(path: str):
    """(host spans, {device plane: [(name, start_ns, end_ns)]})."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices[plane.name] = evs
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return spans, devices


def reduce_events(spans, devices, kernels=()) -> Optional[Reduction]:
    """``kernels``: base names whose individual calls are kept."""
    active = {k: v for k, v in devices.items() if v}
    if not active:
        return None
    d0 = min(s for v in active.values() for _, s, _ in v)
    d1 = max(e for v in active.values() for _, _, e in v)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    # a host window that no device op falls in means the two clocks were
    # not aligned: fall back to the device ops' extent
    w0, w1 = win[0] if win and win[0][0] < d1 and d0 < win[0][1] else (d0, d1)
    ops: Dict[str, float] = {}
    kern: Dict[str, List[Tuple[float, float]]] = {}
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    for i, (plane, evs) in enumerate(sorted(active.items())):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                   if e > w0 and s < w1]
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged)
        for n, s, e in clipped:
            b = base_name(n)
            if b not in _CONTAINERS:
                k = op_label(n)
                ops[k] = ops.get(k, 0.0) + (e - s) * 1e-9 / len(active)
            if b in kernels:
                kern.setdefault(b, []).append(((s - w0) * 1e-9,
                                               (e - s) * 1e-9))
        if i == 0:
            prev = w0
            for s, e in merged + [(w1, w1)]:
                if s > prev:
                    gaps.append((_label((prev + s) / 2, spans),
                                 (s - prev) * 1e-9))
                prev = max(prev, e)
    return Reduction(window_s=(w1 - w0) * 1e-9,
                     busy_s=busy * 1e-9 / len(active), devices=len(active),
                     ops=ops, kernels=kern, gaps=gaps,
                     spans=[(n, (s - w0) * 1e-9, (e - w0) * 1e-9)
                            for n, s, e in spans])


def reduce(path: str, kernels=()) -> Optional[Reduction]:
    """The reduction of one ``.xplane.pb``; None when no device ran."""
    return reduce_events(*read_events(path), kernels=kernels)

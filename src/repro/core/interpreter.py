"""Tier-1 runtime profiler: dead stores / silent stores / silent loads in
an executing JAX program (paper §4-§5, adapted per DESIGN.md §2).

The program's jaxpr is interpreted op by op against a modeled flat address
space: every equation output is a STORE over a buffer placed by a reusing
allocator (buffers free at last use, addresses recycle — the moral
equivalent of the mutable heap JXPerf watches), every operand read is a
LOAD. Memory events stream through the shared event substrate
(repro.core.events): a PMU-style geometric sampler, the paper's reservoir
watchpoints, traps classified per Definitions 1-3 with ⟨C1,C2⟩
attribution into one findings.WasteProfile.

Multi-epoch profiling is trace→replay: the jaxpr is evaluated concretely
ONCE while recording a flat EventTrace (address, extent, value reference,
context per access); epochs 2..N replay that trace through a fresh-epoch
EventEngine. The program is deterministic, so replaying the recorded
stream is event-for-event identical to re-interpreting it — minus the N×
primitive re-binding, which is where all the interpreter time goes
(benchmarks/overhead.py: tier1_replay vs tier1_reinterp). Epoch semantics
are unchanged: each epoch is a GC epoch (watchpoints never cross it),
scan/while/cond/pjit/remat bodies are interpreted recursively with buffer
identity preserved across iterations, so a linear search in a scan traps
exactly like the paper's ``contains()`` case.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np
from jax.extend.core import Literal

from repro.configs.base import ProfilerConfig
from repro.core.context import context_of_eqn
from repro.core.events import (LOAD, STORE, EventEngine, EventTrace,
                               MemEvent)
from repro.core.findings import WasteProfile

# the unified profile IS the Tier-1 report (seed `Report` name kept)
Report = WasteProfile


# ----------------------------------------------------------------------
class Allocator:
    """Flat address space with size-class recycling (heap analogue)."""

    def __init__(self):
        self.next = 0
        self.free_lists: Dict[int, List[int]] = {}

    def alloc(self, nelems: int) -> int:
        fl = self.free_lists.get(nelems)
        if fl:
            return fl.pop()
        addr = self.next
        self.next += max(nelems, 1)
        return addr

    def free(self, addr: int, nelems: int) -> None:
        self.free_lists.setdefault(nelems, []).append(addr)


@dataclass
class Buffer:
    addr: int
    nelems: int
    itemsize: int


_CONTROL_PRIMS = {"scan", "while", "cond"}


def _inner_closed_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            return eqn.params[key]
    return None


class JxInterpreter:
    """Profile fn(*args) and produce a :class:`WasteProfile`."""

    def __init__(self, cfg: Optional[ProfilerConfig] = None):
        self.cfg = cfg or ProfilerConfig(enabled=True)
        self.engine = EventEngine(self.cfg, tier=1)
        self.trace: Optional[EventTrace] = None

    # ------------------------------------------------------------------
    def profile(self, fn, *args, epochs: int = 1,
                replay: bool = True) -> WasteProfile:
        """Profile `epochs` identical executions of fn(*args).

        replay=True (default): interpret once recording an EventTrace,
        then replay it for the remaining epochs. replay=False keeps the
        seed behaviour — full re-interpretation every epoch — and exists
        as the benchmark baseline; both give identical profiles at a
        fixed seed because the replayed stream IS the recorded stream.

        Memory trade: the recorded trace holds every intermediate value
        by reference until profiling ends, so peak host memory is the
        program's *total* intermediate footprint rather than its live
        set. Tier-1 is the offline analysis mode and its subjects are
        deliberately small (DESIGN.md §2); for a memory-constrained
        multi-epoch profile pass replay=False to trade time back.
        """
        closed = jax.make_jaxpr(fn)(*args)
        flat, _ = jax.tree_util.tree_flatten(args)
        flat = [np.asarray(x) for x in flat]
        record = replay and epochs > 1
        for epoch in range(epochs):
            self.alloc = Allocator()
            self.engine.reset_epoch()          # GC-epoch semantics
            if epoch == 0 or not replay:
                self.trace = EventTrace() if record else None
                self._eval_jaxpr(closed.jaxpr, closed.consts, flat, None)
                record = False                 # only the first epoch records
            else:
                self.engine.replay(self.trace)
        return self.engine.finalize()

    # ------------------------------------------------------------------
    def _emit(self, kind: str, buf: Buffer, val: np.ndarray, ctx) -> None:
        ev = MemEvent(kind=kind, address=buf.addr, nelems=buf.nelems,
                      itemsize=buf.itemsize, values=val, ctx=ctx)
        if self.trace is not None:
            self.trace.append(ev)
        self.engine.on_event(ev)

    def _new_buffer(self, val: np.ndarray) -> Buffer:
        return Buffer(self.alloc.alloc(int(val.size)), int(val.size),
                      int(val.dtype.itemsize))

    def _eval_jaxpr(self, jaxpr, consts, args, arg_bufs):
        """Interpret one (sub)jaxpr. arg_bufs: parallel Buffer list for
        `args` (None entries -> fresh input buffers owned by this frame)."""
        env: Dict[Any, np.ndarray] = {}
        bufs: Dict[Any, Buffer] = {}
        owned: List[Buffer] = []

        def read_val(v):
            return np.asarray(v.val) if isinstance(v, Literal) else env[v]

        def read_buf(v):
            return None if isinstance(v, Literal) else bufs.get(v)

        if arg_bufs is None:
            arg_bufs = [None] * len(args)

        for cv, cval in zip(jaxpr.constvars, consts):
            val = np.asarray(cval)
            env[cv] = val
            b = self._new_buffer(val)
            bufs[cv] = b
            owned.append(b)
        for iv, val, b in zip(jaxpr.invars, args, arg_bufs):
            env[iv] = np.asarray(val)
            if b is None:
                b = self._new_buffer(env[iv])
                owned.append(b)
            bufs[iv] = b

        # last-use positions for address recycling within this frame
        last_use: Dict[Any, int] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    last_use[v] = i
        out_set = {v for v in jaxpr.outvars if not isinstance(v, Literal)}

        for i, eqn in enumerate(jaxpr.eqns):
            ctx = context_of_eqn(eqn)
            invals = [read_val(v) for v in eqn.invars]
            inbufs = [read_buf(v) for v in eqn.invars]
            is_call = (eqn.primitive.name in _CONTROL_PRIMS
                       or _inner_closed_jaxpr(eqn) is not None)
            if not is_call:
                for v, b in zip(eqn.invars, inbufs):
                    if b is not None:
                        self._emit(LOAD, b, read_val(v), ctx)

            outvals = self._run_eqn(eqn, invals, inbufs)
            if not isinstance(outvals, (list, tuple)):
                outvals = [outvals]
            for ov, val in zip(eqn.outvars, outvals):
                val = np.asarray(val)
                env[ov] = val
                b = self._new_buffer(val)
                bufs[ov] = b
                owned.append(b)
                if not is_call:
                    self._emit(STORE, b, val, ctx)

            # recycle frame-local dead buffers
            for v in list(bufs):
                if last_use.get(v, -1) <= i and v not in out_set:
                    b = bufs.pop(v)
                    if b in owned:
                        self.alloc.free(b.addr, b.nelems)
                        owned.remove(b)

        outs = [read_val(v) for v in jaxpr.outvars]
        for b in owned:                        # frame exit: release
            self.alloc.free(b.addr, b.nelems)
        return outs

    # ------------------------------------------------------------------
    def _run_eqn(self, eqn, invals, inbufs):
        prim = eqn.primitive
        name = prim.name
        if name == "scan":
            return self._run_scan(eqn, invals, inbufs)
        if name == "while":
            return self._run_while(eqn, invals, inbufs)
        if name == "cond":
            return self._run_cond(eqn, invals, inbufs)
        inner = _inner_closed_jaxpr(eqn)
        if inner is not None:
            cj = inner
            if hasattr(cj, "jaxpr"):
                return self._eval_jaxpr(cj.jaxpr, cj.consts, invals, inbufs)
            return self._eval_jaxpr(cj, [], invals, inbufs)
        out = prim.bind(*invals, **eqn.params)
        return out if prim.multiple_results else [out]

    def _run_scan(self, eqn, invals, inbufs):
        p = eqn.params
        cj = p["jaxpr"]
        nc, ncar, length = p["num_consts"], p["num_carry"], p["length"]
        consts, cbufs = invals[:nc], inbufs[:nc]
        carry = [np.asarray(x) for x in invals[nc:nc + ncar]]
        xs = invals[nc + ncar:]
        ys_acc: List[List[np.ndarray]] = []
        idxs = (range(length - 1, -1, -1) if p.get("reverse")
                else range(length))
        for t in idxs:
            xt = [np.asarray(x)[t] for x in xs]
            args = list(consts) + carry + xt
            bufs = list(cbufs) + [None] * (ncar + len(xt))
            outs = self._eval_jaxpr(cj.jaxpr, cj.consts, args, bufs)
            carry = [np.asarray(o) for o in outs[:ncar]]
            ys_acc.append(outs[ncar:])
        if p.get("reverse"):
            ys_acc.reverse()
        ys = []
        if ys_acc and ys_acc[0]:
            for j in range(len(ys_acc[0])):
                ys.append(np.stack([np.asarray(step[j]) for step in ys_acc]))
        return list(carry) + ys

    def _run_while(self, eqn, invals, inbufs):
        p = eqn.params
        cj, bj = p["cond_jaxpr"], p["body_jaxpr"]
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cconsts, ccb = invals[:cn], inbufs[:cn]
        bconsts, bcb = invals[cn:cn + bn], inbufs[cn:cn + bn]
        state = [np.asarray(x) for x in invals[cn + bn:]]
        iters = 0
        while True:
            pred = self._eval_jaxpr(cj.jaxpr, cj.consts,
                                    list(cconsts) + state,
                                    list(ccb) + [None] * len(state))[0]
            if not bool(np.asarray(pred)):
                break
            state = [np.asarray(o) for o in self._eval_jaxpr(
                bj.jaxpr, bj.consts, list(bconsts) + state,
                list(bcb) + [None] * len(state))]
            iters += 1
            if iters > 100000:
                raise RuntimeError("while loop runaway in interpreter")
        return state

    def _run_cond(self, eqn, invals, inbufs):
        branches = eqn.params["branches"]
        idx = int(np.asarray(invals[0]))
        idx = max(0, min(idx, len(branches) - 1))
        br = branches[idx]
        return self._eval_jaxpr(br.jaxpr, br.consts, invals[1:], inbufs[1:])


def profile_fn(fn, *args, cfg: Optional[ProfilerConfig] = None,
               epochs: int = 1, replay: bool = True) -> WasteProfile:
    """Profile fn(*args) with JXPerf-JAX Tier-1 (trace→replay epochs)."""
    return JxInterpreter(cfg).profile(fn, *args, epochs=epochs,
                                      replay=replay)

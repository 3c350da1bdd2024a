"""Tier-0: static waste lint over closed jaxprs (DESIGN.md § Static tier).

The earliest point in the pipeline where the paper's waste classes are
visible: the jaxpr of a train step / engine tick / prefill *before* XLA
sees it. Tier 2 (`core/hlo_waste.py`) inspects the optimized HLO, which
is post-CSE/DCE and attributes waste to compiler-mangled op names; here
every equation still carries ``source_info``, so findings point at the
Python ``file:line`` that wrote the waste — the static analogue of
JXPerf's ⟨C1,C2⟩ calling contexts.

Rules, each mapped to a paper definition:

  dead_store      (Def. 1)  a ``dynamic_update_slice``/``scatter`` whose
                            written region is fully overwritten by the
                            next store to the same region before any
                            read, or whose result is never read at all;
  silent_store    (Def. 2)  a store of a value provably equal to what is
                            already resident: scatter/DUS of a slice
                            gathered from the same buffer at the same
                            offsets, and x+0 / x-0 / x*1 / x/1 identity
                            chains (the stored value IS the operand);
  redundant_load  (Def. 3)  the same unmutated buffer gathered/sliced
                            with identical indices more than once within
                            a scope, including across ``scan`` iterations
                            (a loop-invariant gather re-executes every
                            trip);
  dead_param      (Def. 1 at allocation granularity)  jaxpr invars that
                            reach no output and no effectful equation —
                            a buffer marshalled in and never read (dead
                            expert weights in MoE dispatch, unused cache
                            leaves).

Equivalence of index chains is decided by hash-consing value numbers
(``jnp`` index normalization clones ``lt/add/select_n`` chains per use,
so var identity is useless); value numbers flow through ``pjit`` /
``remat`` / ``custom_*`` call boundaries, and scan bodies seed their
const invars as loop-invariant so invariance is derivable per equation.

Findings land in the unified ``WasteProfile`` as ``TIER_STATIC = 0``,
mergeable with tiers 1-4 and exportable as SARIF (`core/sarif.py`).
"""
from __future__ import annotations

import numpy as np
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from jax.extend.core import Literal

from repro.core.context import context_of_eqn, user_frames
from repro.core.findings import TIER_STATIC, Finding, WasteProfile

# primitives that *store into* a region of an existing buffer
_STORE_PRIMS = ("dynamic_update_slice", "scatter")
# primitives that *load* a region of a buffer
_LOAD_PRIMS = ("gather", "dynamic_slice", "slice")
# control/call primitives walked recursively, never value-numbered
_CONTROL_PRIMS = ("scan", "while", "cond")
_IDENTITY_PRIMS = {"add": 0.0, "sub": 0.0, "mul": 1.0, "div": 1.0}


def _nbytes(aval) -> float:
    """Bytes of an array aval; 0 for tokens and extended (key) dtypes."""
    if not isinstance(getattr(aval, "dtype", None), np.dtype):
        return 0.0
    return float(np.prod(aval.shape, dtype=np.float64) * aval.dtype.itemsize)


def _src_of(eqn) -> Tuple[Optional[str], int]:
    """Innermost user frame of an eqn: (absolute file path, line)."""
    for f in user_frames(eqn):
        return f.file_name, int(f.start_line)
    return None, 0


def _inner_closed_jaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            return eqn.params[key]
    return None


class _VarInfo:
    """Per-var lint state: value number + loop-invariance in scope."""
    __slots__ = ("vn", "invariant")

    def __init__(self, vn: int, invariant: bool):
        self.vn = vn
        self.invariant = invariant


class _LoadRec:
    __slots__ = ("eqn", "vn", "nbytes", "invariant")

    def __init__(self, eqn, vn, nbytes, invariant):
        self.eqn, self.vn, self.nbytes = eqn, vn, nbytes
        self.invariant = invariant


class JaxprLinter:
    """Walk a ClosedJaxpr and emit a tier-0 :class:`WasteProfile`."""

    def __init__(self, subject: str = "fn"):
        self.subject = subject
        self.profile = WasteProfile(tier=TIER_STATIC)
        self.profile.meta["subject"] = subject
        self._vn_table: Dict[Tuple, int] = {}
        self._next_vn = 0
        # vn -> known scalar constant (literals / broadcast of literal)
        self._const: Dict[int, Any] = {}
        # vn of a load result -> (source vn, index vns, result shape)
        self._load_src: Dict[int, Tuple[int, Tuple[int, ...],
                                        Tuple[int, ...]]] = {}

    # -- value numbering ------------------------------------------------
    def _fresh_vn(self) -> int:
        self._next_vn += 1
        return self._next_vn

    def _fresh_info(self, invariant: bool = True) -> _VarInfo:
        return _VarInfo(self._fresh_vn(), invariant)

    def _vn_of_key(self, key: Tuple) -> int:
        vn = self._vn_table.get(key)
        if vn is None:
            vn = self._fresh_vn()
            self._vn_table[key] = vn
        return vn

    def _lit_info(self, lit: Literal) -> _VarInfo:
        val = np.asarray(lit.val)
        key = ("lit", str(val.dtype), val.shape, val.tobytes())
        vn = self._vn_of_key(key)
        if val.size == 1:
            self._const.setdefault(vn, val.reshape(()).item())
        return _VarInfo(vn, True)

    @staticmethod
    def _params_key(params: Dict[str, Any]) -> str:
        return repr(sorted(params.items(), key=lambda kv: kv[0]))

    # -- findings -------------------------------------------------------
    def _flag(self, kind: str, eqn, *, bytes=0.0, count=1, c2_eqn=None,
              fraction=0.0, **meta) -> None:
        c1 = context_of_eqn(eqn)
        c2 = context_of_eqn(c2_eqn) if c2_eqn is not None else ()
        f, line = _src_of(eqn)
        if f is not None:
            meta.setdefault("file", f)
            meta.setdefault("line", line)
        meta.setdefault("subject", self.subject)
        self.profile.add(Finding(kind=kind, tier=TIER_STATIC, c1=c1, c2=c2,
                                 count=count, bytes=float(bytes),
                                 fraction=fraction, meta=meta))

    def _flag_dead_param(self, label: str, aval, where: str) -> None:
        self.profile.add(Finding(
            kind="dead_param", tier=TIER_STATIC,
            c1=(f"{self.subject}:{label}",), c2=(where,),
            bytes=_nbytes(aval),
            meta={"path": f"{self.subject}:{label}", "subject": self.subject,
                  "shape": str(getattr(aval, "shape", "?")),
                  "rule": "invar reaches no output"}))

    # -- entry ----------------------------------------------------------
    def lint(self, closed, arg_labels: Optional[Sequence[str]] = None
             ) -> WasteProfile:
        jaxpr = closed.jaxpr
        infos = [self._fresh_info(invariant=False)
                 for _ in list(jaxpr.constvars) + list(jaxpr.invars)]
        labels: Dict[Any, str] = {}
        if arg_labels:
            for v, lab in zip(jaxpr.invars, arg_labels):
                labels[v] = lab
        self._walk(jaxpr, infos, mult=1.0, scan_len=None,
                   labels=labels, top=True)
        return self.profile

    # -- the walker -----------------------------------------------------
    def _walk(self, jaxpr, in_infos: List[_VarInfo], *, mult: float,
              scan_len: Optional[int], labels: Dict[Any, str],
              top: bool = False,
              shared_loads: Optional[List[_LoadRec]] = None
              ) -> Tuple[List[_VarInfo], set]:
        """Lint one (sub)jaxpr. Returns (outvar infos, live invar set).

        ``shared_loads``: transparent call boundaries (pjit/remat/
        custom_*) pass their caller's load list so identical loads in
        sibling calls coalesce — ``jnp.take`` nests its gather inside a
        fresh pjit per call site, so per-scope lists would never see the
        duplicate. When set, the dup/loop-invariant epilogue is the
        owner's job, not ours."""
        env: Dict[Any, _VarInfo] = {}
        for v, info in zip(list(jaxpr.constvars) + list(jaxpr.invars),
                           in_infos):
            env[v] = info

        def info_of(v) -> _VarInfo:
            if isinstance(v, Literal):
                return self._lit_info(v)
            return env[v]

        use_count: Dict[Any, int] = {}
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    use_count[v] = use_count.get(v, 0) + 1
        outvar_set = {v for v in jaxpr.outvars if not isinstance(v, Literal)}

        producer: Dict[Any, Any] = {}          # var -> producing eqn
        owns_loads = shared_loads is None
        loads: List[_LoadRec] = [] if owns_loads else shared_loads
        store_eqns: List[Any] = []
        dead_stores: set = set()               # id(eqn) flagged dead
        silent_stores: set = set()             # id(eqn) flagged silent

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            infos = [info_of(v) for v in eqn.invars]
            inner = _inner_closed_jaxpr(eqn)

            if name in _CONTROL_PRIMS or inner is not None:
                out_infos = self._walk_call(eqn, infos, mult=mult,
                                            scan_len=scan_len,
                                            labels=labels, loads=loads)
            else:
                out_infos = self._number_eqn(eqn, infos)
                self._check_eqn(eqn, infos, out_infos, info_of, producer,
                                use_count, mult=mult, loads=loads,
                                store_eqns=store_eqns,
                                dead_stores=dead_stores,
                                silent_stores=silent_stores,
                                outvar_set=outvar_set)
            for ov, oi in zip(eqn.outvars, out_infos):
                env[ov] = oi
                producer[ov] = eqn

        # ---- liveness (reverse pass) ----------------------------------
        live: set = set(outvar_set)
        for eqn in reversed(jaxpr.eqns):
            if (any(ov in live for ov in eqn.outvars)
                    or bool(getattr(eqn, "effects", ()))):
                for v in eqn.invars:
                    if not isinstance(v, Literal):
                        live.add(v)

        # ---- unused store results -> dead stores ----------------------
        for eqn in store_eqns:
            if id(eqn) in dead_stores:
                continue
            if not any(ov in live for ov in eqn.outvars):
                dead_stores.add(id(eqn))
                upd = eqn.invars[2 if eqn.primitive.name == "scatter"
                                 else 1]
                self._flag("dead_store", eqn,
                           bytes=_nbytes(upd.aval) * mult,
                           count=max(int(mult), 1),
                           rule="store result never read")

        # ---- estimator counters for stores ----------------------------
        for eqn in store_eqns:
            self.profile.observe("dead_store", id(eqn) in dead_stores)
            self.profile.observe("silent_store", id(eqn) in silent_stores)

        # ---- duplicate / loop-invariant loads -------------------------
        if not owns_loads:
            return [info_of(v) for v in jaxpr.outvars], live
        by_vn: Dict[int, List[_LoadRec]] = {}
        for rec in loads:
            by_vn.setdefault(rec.vn, []).append(rec)
        for vn, recs in by_vn.items():
            dup = len(recs) > 1
            loop_inv = (not dup and recs[0].invariant
                        and scan_len is not None and scan_len > 1)
            for j, rec in enumerate(recs):
                self.profile.observe("redundant_load",
                                     (dup and j > 0) or loop_inv)
            if dup:
                extra = sum(r.nbytes for r in recs[1:]) * mult
                self._flag("redundant_load", recs[0].eqn, bytes=extra,
                           count=(len(recs) - 1) * max(int(mult), 1),
                           c2_eqn=recs[1].eqn,
                           rule="same buffer loaded at identical indices "
                                f"{len(recs)}x in one scope")
            elif loop_inv:
                rec = recs[0]
                outer = mult / scan_len
                self._flag("redundant_load", rec.eqn,
                           bytes=rec.nbytes * (scan_len - 1) * outer,
                           count=max(int((scan_len - 1) * outer), 1),
                           fraction=1.0 - 1.0 / scan_len,
                           rule=f"loop-invariant load re-executed by "
                                f"scan[length={scan_len}]")

        # ---- dead invars ----------------------------------------------
        if top:
            for i, v in enumerate(jaxpr.invars):
                self.profile.observe("dead_param", v not in live)
                if v not in live:
                    self._flag_dead_param(labels.get(v, f"arg{i}"), v.aval,
                                          where="top-level jaxpr")
        return [info_of(v) for v in jaxpr.outvars], live

    # -- per-eqn numbering ----------------------------------------------
    def _number_eqn(self, eqn, infos: List[_VarInfo]) -> List[_VarInfo]:
        name = eqn.primitive.name
        invariant = (all(i.invariant for i in infos)
                     and not getattr(eqn, "effects", ()))
        key = (name, self._params_key(eqn.params),
               tuple(i.vn for i in infos))
        if len(eqn.outvars) == 1:
            vns = [self._vn_of_key(key)]
        else:
            vns = [self._vn_of_key(key + ("#out", k))
                   for k in range(len(eqn.outvars))]
        # constant propagation for the silent-identity rule
        if name in ("broadcast_in_dim", "convert_element_type") \
                and infos and infos[0].vn in self._const:
            self._const.setdefault(vns[0], self._const[infos[0].vn])
        return [_VarInfo(vn, invariant) for vn in vns]

    # -- local rules ----------------------------------------------------
    def _check_eqn(self, eqn, infos, out_infos,
                   info_of: Callable[[Any], _VarInfo], producer, use_count,
                   *, mult, loads, store_eqns, dead_stores, silent_stores,
                   outvar_set) -> None:
        name = eqn.primitive.name

        # ---- identity chains: store of a provably-equal value ---------
        if name in _IDENTITY_PRIMS and len(eqn.invars) == 2:
            ident = _IDENTITY_PRIMS[name]
            for xi, ci in ((0, 1), (1, 0)):
                if name in ("sub", "div") and ci == 0:
                    continue       # 0-x / 1/x are not identities
                cval = self._const.get(infos[ci].vn)
                xv = eqn.invars[xi]
                if cval is not None and cval == ident \
                        and not isinstance(xv, Literal) \
                        and tuple(getattr(xv.aval, "shape", ())) \
                        == tuple(eqn.outvars[0].aval.shape):
                    self.profile.observe("silent_store", True)
                    self._flag(
                        "silent_store", eqn,
                        bytes=_nbytes(eqn.outvars[0].aval) * mult,
                        count=max(int(mult), 1),
                        rule=f"identity {name} with {cval!r}: stores a "
                             f"value equal to the resident operand")
                    # the result IS the operand: share its value number
                    out_infos[0].vn = infos[xi].vn
                    return

        # ---- loads ----------------------------------------------------
        if name in _LOAD_PRIMS:
            nb = _nbytes(eqn.outvars[0].aval)
            loads.append(_LoadRec(eqn, out_infos[0].vn, nb,
                                  all(i.invariant for i in infos)))
            src_vn = infos[0].vn
            if name == "slice":    # indices live in params, not operands
                idx_vns: Tuple[int, ...] = (self._vn_of_key(
                    ("slice-idx", self._params_key(eqn.params))),)
            else:
                idx_vns = tuple(i.vn for i in infos[1:])
            self._load_src[out_infos[0].vn] = (
                src_vn, idx_vns, tuple(eqn.outvars[0].aval.shape))
            return

        # ---- stores ---------------------------------------------------
        if name not in _STORE_PRIMS:
            return
        store_eqns.append(eqn)
        if name == "dynamic_update_slice":
            opnd, upd = eqn.invars[0], eqn.invars[1]
            opnd_info, upd_info = infos[0], infos[1]
            idx_vns = tuple(i.vn for i in infos[2:])
        else:                                   # scatter (overwrite mode)
            opnd, upd = eqn.invars[0], eqn.invars[2]
            opnd_info, upd_info = infos[0], infos[2]
            idx_vns = (infos[1].vn,)

        # silent store: the update was gathered from this very buffer at
        # these very offsets (Def. 2, provable statically)
        src = self._load_src.get(upd_info.vn)
        if src is not None:
            src_vn, load_idx_vns, load_shape = src
            if src_vn == opnd_info.vn and load_idx_vns == idx_vns \
                    and load_shape == tuple(upd.aval.shape):
                silent_stores.add(id(eqn))
                self._flag("silent_store", eqn,
                           bytes=_nbytes(upd.aval) * mult,
                           count=max(int(mult), 1),
                           rule="stores the slice it gathered from the "
                                "same offsets (write-back of resident "
                                "value)")

        # dead store: this store overwrites the exact region a previous
        # store (whose result nobody else read) just wrote (Def. 1)
        prev = producer.get(opnd)
        if (prev is not None and prev.primitive.name == name
                and use_count.get(opnd, 0) == 1
                and opnd not in outvar_set
                and id(prev) not in dead_stores):
            if name == "dynamic_update_slice":
                prev_idx = tuple(info_of(v).vn for v in prev.invars[2:])
                prev_upd = prev.invars[1]
            else:
                prev_idx = (info_of(prev.invars[1]).vn,)
                prev_upd = prev.invars[2]
            if prev_idx == idx_vns and tuple(prev_upd.aval.shape) \
                    == tuple(upd.aval.shape):
                dead_stores.add(id(prev))
                self._flag("dead_store", prev,
                           bytes=_nbytes(prev_upd.aval) * mult,
                           count=max(int(mult), 1), c2_eqn=eqn,
                           rule="written region fully overwritten before "
                                "any read")

    # -- call recursion -------------------------------------------------
    def _walk_call(self, eqn, infos: List[_VarInfo], *, mult, scan_len,
                   labels, loads) -> List[_VarInfo]:
        name = eqn.primitive.name
        if name == "scan":
            return self._walk_scan(eqn, infos, mult=mult, labels=labels)
        if name == "while":
            p = eqn.params
            cj, bj = p["cond_jaxpr"], p["body_jaxpr"]
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            state = [self._fresh_info(invariant=False)
                     for _ in range(len(infos) - cn - bn)]
            self._walk(cj.jaxpr,
                       [self._fresh_info() for _ in cj.jaxpr.constvars]
                       + infos[:cn] + state,
                       mult=mult, scan_len=None, labels={})
            self._walk(bj.jaxpr,
                       [self._fresh_info() for _ in bj.jaxpr.constvars]
                       + infos[cn:cn + bn] + state,
                       mult=mult, scan_len=None, labels={})
            return [self._fresh_info() for _ in eqn.outvars]
        if name == "cond":
            for br in eqn.params["branches"]:
                self._walk(br.jaxpr,
                           [self._fresh_info() for _ in br.jaxpr.constvars]
                           + infos[1:],
                           mult=mult, scan_len=scan_len, labels={})
            return [self._fresh_info() for _ in eqn.outvars]
        # pjit / remat / closed_call / custom_jvp / custom_vjp: value
        # numbers and invariance flow straight through the boundary
        cj = _inner_closed_jaxpr(eqn)
        inner, consts = (cj.jaxpr, cj.consts) if hasattr(cj, "jaxpr") \
            else (cj, [])
        const_infos = [self._fresh_info() for _ in inner.constvars]
        # extra caller operands beyond the inner signature (custom_*
        # bookkeeping args) are dropped positionally from the left
        n = len(inner.invars)
        off = max(len(infos) - n, 0)
        arg_infos = infos[off:]
        arg_infos += [self._fresh_info()
                      for _ in range(n - len(arg_infos))]
        inner_labels = {iv: labels[ov]
                        for iv, ov in zip(inner.invars, eqn.invars[off:])
                        if not isinstance(ov, Literal) and ov in labels}
        outs, _ = self._walk(inner, const_infos + arg_infos, mult=mult,
                             scan_len=scan_len, labels=inner_labels,
                             shared_loads=loads)
        if len(outs) == len(eqn.outvars):
            return outs
        return [self._fresh_info() for _ in eqn.outvars]

    def _walk_scan(self, eqn, infos: List[_VarInfo], *, mult, labels
                   ) -> List[_VarInfo]:
        p = eqn.params
        cj = p["jaxpr"]
        nc, ncar, length = p["num_consts"], p["num_carry"], p["length"]
        body = cj.jaxpr
        const_infos = [self._fresh_info() for _ in body.constvars]
        # consts are loop-invariant BY DEFINITION inside the body; carry
        # and xs change per iteration
        arg_infos = (
            [_VarInfo(i.vn, True) for i in infos[:nc]]
            + [self._fresh_info(invariant=False)
               for _ in range(len(body.invars) - nc)])
        inner_labels = {iv: labels[ov]
                        for iv, ov in zip(body.invars, eqn.invars)
                        if not isinstance(ov, Literal) and ov in labels}
        _, live = self._walk(body, const_infos + arg_infos,
                             mult=mult * max(length, 1),
                             scan_len=length if length > 1 else None,
                             labels=inner_labels)
        # dead scan inputs: a const/xs buffer marshalled into every
        # iteration but never read by the body (the MoE dead-expert case
        # when routing ignores an expert's weights)
        for j, iv in enumerate(body.invars):
            is_carry = nc <= j < nc + ncar
            self.profile.observe("dead_param",
                                 not is_carry and iv not in live)
            if is_carry or iv in live:
                continue
            ov = eqn.invars[j] if j < len(eqn.invars) else None
            lab = inner_labels.get(iv) or (
                labels.get(ov) if ov is not None
                and not isinstance(ov, Literal) else None)
            self._flag_dead_param(
                lab or f"scan arg{j}", iv.aval,
                where=f"scan[length={length}] body "
                      f"({'const' if j < nc else 'xs'} operand unused)")
        return [self._fresh_info() for _ in eqn.outvars]


# ----------------------------------------------------------------------
def lint_jaxpr(closed, *, subject: str = "fn",
               arg_labels: Optional[Sequence[str]] = None) -> WasteProfile:
    """Lint a ClosedJaxpr; returns a tier-0 WasteProfile."""
    return JaxprLinter(subject).lint(closed, arg_labels=arg_labels)


def lint_fn(fn, *args, subject: str = "fn",
            arg_labels: Optional[Sequence[str]] = None) -> WasteProfile:
    """``make_jaxpr`` + lint. ``args`` may be arrays or ShapeDtypeStructs
    (the jaxpr is traced abstractly — no compute, no allocation).

    ``arg_labels`` defaults to the flattened pytree key paths of ``args``
    so dead-parameter findings name the buffer
    (``arg0/main/b0_moe/moe/w_up``) instead of a positional index."""
    closed = jax.make_jaxpr(fn)(*args)
    if arg_labels is None:
        arg_labels = arg_tree_labels(args)
    return lint_jaxpr(closed, subject=subject, arg_labels=arg_labels)


def arg_tree_labels(args) -> List[str]:
    """Flattened key-path labels for a tuple of pytree args (the order
    ``make_jaxpr`` assigns invars)."""
    labels = []
    for i, a in enumerate(args):
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        for path, _ in flat:
            labels.append(f"arg{i}{jax.tree_util.keystr(path)}"
                          if path else f"arg{i}")
    return labels

"""DJXPerf-style object registry: allocation-site provenance for every
long-lived buffer in the system (DESIGN.md § Object tier).

JXPerf bills waste to flat addresses; DJXPerf (arXiv 2104.03388) showed
the actionable unit is the *object* — the allocation a developer can
rename, resize or delete. This registry is that mapping for the JAX
port: every KV pool page, parameter tensor, optimizer-state leaf and
speculative draft window registers an :class:`ObjectRecord` carrying

- a stable human-readable name (``replica0/kv/page7``,
  ``params/main.b0_dense.attn.wq.w``),
- its kind (``kv_page`` / ``param`` / ``opt_state`` / ``draft_window``),
- byte size and the **allocation site** (file:line:function of the
  registering caller — ``PageAllocator.alloc``, ``params.init_tree``,
  ``train/state.py create``), and
- an optional zero-argument ``reader`` returning the current contents
  as a numpy array, which is what lets `core/replicas.py` content-hash
  live objects without the registry ever holding device buffers.

Tiers 0-4 bill waste bytes to objects through
``WasteProfile.bill_object``; the registry itself is pure bookkeeping
(one dict insert per alloc) so it can stay on in production serving.
"""
from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

OBJECT_KINDS = ("kv_page", "param", "opt_state", "draft_window")


@dataclass
class ObjectRecord:
    """One registered long-lived buffer with allocation-site provenance."""
    oid: int
    name: str
    kind: str
    nbytes: int
    file: str
    line: int
    func: str
    meta: Dict[str, Any] = field(default_factory=dict)
    reader: Optional[Callable[[], Any]] = None

    @property
    def site(self) -> str:
        """Machine-portable allocation site (file basename, like the
        tier-0 lint contexts)."""
        return f"{os.path.basename(self.file)}:{self.line}"

    @property
    def object_key(self) -> str:
        """Stable string key the WasteProfile object table coalesces on
        (kind|name|alloc-site) — the §5.6 analogue for objects."""
        return f"{self.kind}|{self.name}|{self.site}"

    @property
    def owner(self) -> str:
        """Leading path segment of the name (fleet replica / subsystem)."""
        return self.name.split("/", 1)[0]


class ObjectRegistry:
    """Live-object table. register() captures the caller's file:line as
    the allocation site; release() retires an object (freed page,
    dropped window) so replica scans only see live buffers."""

    def __init__(self) -> None:
        self._records: Dict[int, ObjectRecord] = {}
        self._next_oid = 0

    def __len__(self) -> int:
        return len(self._records)

    def register(self, name: str, kind: str, nbytes: int, *,
                 reader: Optional[Callable[[], Any]] = None,
                 meta: Optional[Dict[str, Any]] = None,
                 depth: int = 1) -> ObjectRecord:
        """Register one object; the allocation site is the caller's
        frame (``depth`` frames up — pass 2 from a helper that registers
        on someone else's behalf)."""
        assert kind in OBJECT_KINDS, kind
        fr = sys._getframe(depth)
        rec = ObjectRecord(oid=self._next_oid, name=name, kind=kind,
                           nbytes=int(nbytes), file=fr.f_code.co_filename,
                           line=fr.f_lineno, func=fr.f_code.co_name,
                           meta=dict(meta or {}), reader=reader)
        self._next_oid += 1
        self._records[rec.oid] = rec
        return rec

    def release(self, oid: int) -> None:
        self._records.pop(oid, None)

    def get(self, oid: int) -> Optional[ObjectRecord]:
        return self._records.get(oid)

    def live(self, kind: Optional[str] = None) -> List[ObjectRecord]:
        recs = [r for r in self._records.values()
                if kind is None or r.kind == kind]
        return sorted(recs, key=lambda r: r.name)

    def nbytes_live(self, kind: Optional[str] = None) -> int:
        return sum(r.nbytes for r in self.live(kind))


def register_tree(registry: Optional[ObjectRegistry], owner: str, tree,
                  *, kind: str = "param",
                  meta: Optional[Dict[str, Any]] = None
                  ) -> List[ObjectRecord]:
    """Register every array leaf of a pytree under ``owner/<path>``.

    Used to attribute one physical tree to a logical owner — e.g. the
    fleet driver registers the (shared, in-process) parameter tree once
    per replica, which is exactly the layout a multi-host fleet would
    materialize; the replica detector then reports those copies as the
    bit-identical weight replicas they would be.
    """
    if registry is None:
        return []
    import jax
    import numpy as np
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not hasattr(leaf, "nbytes"):
            continue
        name = f"{owner}/" + jax.tree_util.keystr(path).strip("[]'").replace(
            "']['", ".")
        out.append(registry.register(
            name, kind, int(leaf.nbytes),
            reader=(lambda a=leaf: np.asarray(a)),
            meta=meta, depth=2))
    return out

"""Calling contexts and ⟨C1,C2⟩ pair bookkeeping (paper §5.5-§5.6).

A context is the full user-code call path of a jaxpr equation
(``source_info`` traceback), ending at the primitive — the analogue of
``packageA.classB.methodC:line -> ... -> String.equals():line``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

from jax._src import source_info_util


def user_frames(eqn):
    """User-code frames of a jaxpr eqn's traceback, innermost first
    (empty when the eqn was traced without one)."""
    tb = eqn.source_info.traceback
    if tb is None:
        return []
    return list(source_info_util.user_frames(tb))


def context_of_eqn(eqn, max_frames: int = 12) -> Tuple[str, ...]:
    """Full calling context for a jaxpr eqn from its source_info."""
    frames = [f"{f.file_name.split('/')[-1]}:{f.start_line}:{f.function_name}"
              for f in user_frames(eqn)[:max_frames]]
    frames.reverse()                      # outermost -> innermost
    frames.append(str(eqn.primitive.name))
    return tuple(frames)


def fmt_context(ctx: Tuple[str, ...]) -> str:
    return " -> ".join(ctx)


@dataclass
class PairStats:
    count: int = 0
    bytes: float = 0.0


class PairTable:
    """⟨C_watch, C_trap⟩ -> stats, mergeable across shards (§5.6: two pairs
    coalesce iff both contexts match)."""

    def __init__(self):
        self.pairs: Dict[Tuple[Tuple[str, ...], Tuple[str, ...]], PairStats] = {}

    def add(self, c1, c2, nbytes: float) -> None:
        st = self.pairs.setdefault((c1, c2), PairStats())
        st.count += 1
        st.bytes += nbytes

    def merge(self, other: "PairTable") -> "PairTable":
        for k, v in other.pairs.items():
            st = self.pairs.setdefault(k, PairStats())
            st.count += v.count
            st.bytes += v.bytes
        return self

    def top(self, k: int = 10):
        items = sorted(self.pairs.items(), key=lambda kv: -kv[1].bytes)
        return items[:k]

    @property
    def total_bytes(self) -> float:
        return sum(v.bytes for v in self.pairs.values())

    @property
    def total_count(self) -> int:
        return sum(v.count for v in self.pairs.values())

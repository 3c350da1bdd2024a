"""Activation-sharding context.

Models are written mesh-agnostic and call ``shard(x, kind)`` at layer
boundaries. The active distribution strategy (set by the step factories in
``repro.train.step`` / ``repro.serve.decode``) maps each activation *kind*
to a PartitionSpec; with no strategy active, ``shard`` is the identity, so
all model code runs unmodified on a single CPU device.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax

_SHARDER: contextvars.ContextVar = contextvars.ContextVar("sharder", default=None)


class Sharder:
    """Maps activation kinds -> PartitionSpec under a given mesh."""

    def __init__(self, mesh, act_specs, batch_axes=("data",)):
        self.mesh = mesh
        self.act_specs = dict(act_specs)
        self.batch_axes = tuple(batch_axes)

    def _divisible(self, shape, spec) -> bool:
        for dim, names in zip(shape, tuple(spec) + (None,) * len(shape)):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            n = 1
            for a in names:
                n *= self.mesh.shape[a]
            if dim % n:
                return False
        return True

    def constrain(self, x, kind: str):
        spec = self.act_specs.get(kind)
        if spec is None:
            return x
        if len(spec) > x.ndim or not self._divisible(x.shape, spec):
            # never let GSPMD pad implicitly (keeps cost_analysis honest);
            # undersized smoke shapes simply stay replicated
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))


@contextlib.contextmanager
def sharding_ctx(sharder: Optional[Sharder]):
    tok = _SHARDER.set(sharder)
    try:
        yield
    finally:
        _SHARDER.reset(tok)


def shard(x, kind: str):
    s = _SHARDER.get()
    if s is None:
        return x
    return s.constrain(x, kind)


def current_sharder() -> Optional[Sharder]:
    return _SHARDER.get()


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

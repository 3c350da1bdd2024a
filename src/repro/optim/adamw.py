"""AdamW with decoupled weight decay, pytree-native, GSPMD-friendly.

State layout (ZeRO-1): the f32 master params and both moments live fully
sharded (see repro.sharding.rules.opt_specs); the bf16 compute params are
re-materialized from the master after each update with the model's own
(strategy-specific) sharding.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig


class AdamWState(NamedTuple):
    m: Any
    v: Any


def init(params: Any) -> AdamWState:
    """Zero moments (all bit-identical zeros at init: `train/state.py`
    registers them as ``opt_state`` objects, the replica-detector demo of
    state that could lazy-materialize on first non-zero update)."""
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return AdamWState(m=jax.tree_util.tree_map(zeros, params),
                      v=jax.tree_util.tree_map(zeros, params))


def update(tc: TrainConfig, grads: Any, state: AdamWState, master: Any,
           lr: jax.Array, step: jax.Array):
    """Returns (new_master, new_state). All math in f32."""
    b1, b2, eps, wd = tc.b1, tc.b2, tc.eps, tc.weight_decay
    count = step.astype(jnp.float32) + 1.0
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m_new = b1 * m + (1.0 - b1) * g
        v_new = b2 * v + (1.0 - b2) * jnp.square(g)
        mhat = m_new / c1
        vhat = v_new / c2
        delta = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        return p - lr * delta, m_new, v_new

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    flat_p = treedef.flatten_up_to(master)
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamWState(m=new_m, v=new_v)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    # reduce, not builtin sum(): sum() seeds with literal 0, emitting a
    # zero-add equation (tier-0 silent_store finding)
    sq = [jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves]
    return jnp.sqrt(functools.reduce(jnp.add, sq))


def clip_by_global_norm(tree: Any, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm

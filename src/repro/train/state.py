"""Train state: bf16 compute params + f32 master/moments (ZeRO-1 layout)."""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any          # compute dtype (bf16), strategy.param_specs
    master: Any          # f32, fully sharded (opt_specs)
    opt: adamw.AdamWState  # f32, fully sharded
    step: jax.Array      # scalar int32


def create(model, key, compute_dtype=jnp.bfloat16,
           registry=None, strategy=None) -> TrainState:
    """With an object registry (core/objects.py) the compute/master
    trees register as ``param`` objects and the moments as
    ``opt_state`` objects here, where they are allocated.

    With a sharding ``strategy`` the state is created sharded: one jit
    whose ``out_shardings`` are `state_shardings`, so no device ever
    holds the whole state (14 B/param would not fit one chip)."""
    def build(key):
        master = model.init(key, dtype=jnp.float32)
        return TrainState(
            params=jax.tree_util.tree_map(
                lambda p: p.astype(compute_dtype), master),
            master=master, opt=adamw.init(master),
            step=jnp.zeros((), jnp.int32))

    if strategy is None:
        state = build(key)
    else:
        state = jax.jit(build, out_shardings=state_shardings(
            model, strategy))(key)
    if registry is not None:
        from repro.core.objects import register_tree
        register_tree(registry, "train/master", state.master, kind="param")
        register_tree(registry, "train/params", state.params, kind="param")
        register_tree(registry, "opt/m", state.opt.m, kind="opt_state")
        register_tree(registry, "opt/v", state.opt.v, kind="opt_state")
    return state


def abstract(model, compute_dtype=jnp.bfloat16) -> TrainState:
    """ShapeDtypeStruct state (no allocation) for AOT lowering."""
    master = model.abstract_params(jnp.float32)
    cast = lambda dt: jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dt), master)
    return TrainState(params=cast(compute_dtype), master=master,
                      opt=adamw.AdamWState(m=cast(jnp.float32), v=cast(jnp.float32)),
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def state_specs(model, strategy):
    """PartitionSpec tree matching TrainState."""
    import jax.sharding as shd
    p_specs = strategy.param_specs(model)
    o_specs = strategy.opt_specs(model)
    return TrainState(params=p_specs, master=o_specs,
                      opt=adamw.AdamWState(m=o_specs, v=o_specs),
                      step=shd.PartitionSpec())


def state_shardings(model, strategy):
    """NamedSharding tree matching TrainState on the strategy's mesh."""
    import jax.sharding as shd
    return jax.tree_util.tree_map(
        strategy.named, state_specs(model, strategy),
        is_leaf=lambda x: isinstance(x, shd.PartitionSpec))

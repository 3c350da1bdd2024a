"""Random weights from ``--seed``, made by the benchmark, on the device.

The program under test and the plain reference both read these: the
reference never takes weights the program made. The tree follows the
program's parameter layout (its input format) and the program's own
parameter dtype; every leaf is drawn in one jitted call. Scales: norms
1, the embedding 0.02, an untied head 1/sqrt(d_model), every other
matrix 1/sqrt(fan_in).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, stream: int = 0) -> jax.Array:
    """A JAX key from any whole-number seed (64 bits are kept)."""
    s = int(seed) % (1 << 64)
    k = jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))
    return jax.random.fold_in(k, stream)


def _std(path, shape, d_model: int):
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    if names[-1] == "scale":
        return None
    if names[0] == "embed":
        return 0.02
    if names[0] == "head":
        return 1.0 / math.sqrt(d_model)
    return 1.0 / math.sqrt(shape[-2])


def param_builder(abstract, d_model: int):
    """``build(key)``: the parameter tree for ``abstract`` (a
    ShapeDtypeStruct tree); traceable, so it can sit inside another jit."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def build(k):
        keys = jax.random.split(k, len(flat))
        out = []
        for kk, (path, a) in zip(keys, flat):
            std = _std(path, a.shape, d_model)
            if std is None:
                out.append(jnp.ones(a.shape, a.dtype))
            else:
                out.append((jax.random.normal(kk, a.shape, jnp.float32)
                            * std).astype(a.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    return build


def make_params(abstract, seed: int, d_model: int):
    """The benchmark's weights for ``seed``, made in one jitted call."""
    return jax.jit(param_builder(abstract, d_model))(key(seed))

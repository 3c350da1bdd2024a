"""Sharding strategies: logical param/activation axes -> PartitionSpecs.

Three strategies (DESIGN.md §4):

* ``dp_tp``  — baseline. Batch over ("pod","data"); Megatron column/row TP
  over "model" on flattened feature dims; attention runs with the *query
  sequence* block-sharded over "model" and K/V gathered (GQA keeps K/V
  small), which avoids every head-divisibility problem with zero padding.
* ``fsdp``   — optimized training. Weights/master/moments sharded over
  ("data","model") (largest divisible dim per leaf, ZeRO-3 style); pure-DP
  compute; GSPMD all-gathers block weights inside the scan (overlappable).
* ``tp_serve`` — decoding. Megatron TP weights; KV cache sharded over
  "model" by sequence chunks — each shard computes partial attention and
  the partials combine flash-decoding style (``kernels/sharded.py``).
  For models whose TP-16 bf16 weights exceed one chip's HBM, weights are
  spread over ("data","model") instead (weight-gathered serving).

Divisibility rule: a dim is only sharded if the mesh axis divides it —
otherwise the dim stays replicated (never implicit GSPMD padding, so
cost_analysis FLOPs stay honest). Small leaves (< 64 KiB) replicate.
"""
from __future__ import annotations

import numpy as np
from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.sharding.ctx import Sharder

# priority order in which dims of one leaf may claim a mesh axis
_PRIORITY = ("experts", "vocab", "ffn", "q_feat", "kv_feat", "ssm_inner", "embed")
_SMALL = 16384  # leaves under 16Ki elements stay replicated


def _axis_size(mesh, name) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[a] for a in name]))
    return int(mesh.shape[name])


def _dp_axes(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def leaf_spec_tp(axes: Tuple[Optional[str], ...], shape, mesh) -> PS:
    """Megatron TP: shard the highest-priority divisible feature dim on 'model'."""
    if int(np.prod(shape)) < _SMALL:
        return PS()
    best, best_rank = None, len(_PRIORITY)
    for i, ax in enumerate(axes):
        if ax in _PRIORITY:
            rank = _PRIORITY.index(ax)
            if rank < best_rank and shape[i] % mesh.shape["model"] == 0:
                # embed only ranks for row-parallel second dims; skip embed on
                # dim 0 of 2D weights (keeps column-parallel layout canonical)
                if ax == "embed" and i == 0 and len(shape) > 1:
                    continue
                best, best_rank = i, rank
    spec = [None] * len(shape)
    if best is not None:
        spec[best] = "model"
    return PS(*spec)


def leaf_spec_fsdp(axes, shape, mesh) -> PS:
    """ZeRO-3: shard the largest divisible dim over (data,model) combined,
    else over 'model' alone, else replicate."""
    if int(np.prod(shape)) < _SMALL:
        return PS()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    combined = _axis_size(mesh, ("data", "model")) if "data" in mesh.shape else None
    for i in order:
        if axes[i] == "layers":
            continue
        if combined and shape[i] % combined == 0:
            spec = [None] * len(shape)
            spec[i] = ("data", "model")
            return PS(*spec)
    for i in order:
        if axes[i] == "layers":
            continue
        if shape[i] % mesh.shape["model"] == 0:
            spec = [None] * len(shape)
            spec[i] = "model"
            return PS(*spec)
    return PS()


def _tree_specs(axes_tree, abstract_params, mesh, leaf_fn):
    return jax.tree_util.tree_map(
        lambda ax, p: leaf_fn(ax, p.shape, mesh), axes_tree, abstract_params,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


class Strategy:
    name: str = "base"

    def __init__(self, mesh):
        self.mesh = mesh
        self.dp = _dp_axes(mesh)

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the global batch is sharded over."""
        return self.dp

    # ---- param specs -------------------------------------------------
    def param_specs(self, model) -> Any:
        raise NotImplementedError

    def opt_specs(self, model) -> Any:
        """Fully-sharded specs for master/m/v (ZeRO-1)."""
        return _tree_specs(model.param_axes(), model.abstract_params(),
                           self.mesh, leaf_spec_fsdp)

    # ---- activation specs --------------------------------------------
    def act_specs(self) -> dict:
        raise NotImplementedError

    def sharder(self) -> Sharder:
        return Sharder(self.mesh, self.act_specs(), self.batch_axes)

    def named(self, spec: PS) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---- data specs ----------------------------------------------------
    def batch_spec(self) -> PS:
        return PS(self.dp)


class DpTp(Strategy):
    name = "dp_tp"

    def param_specs(self, model):
        return _tree_specs(model.param_axes(), model.abstract_params(),
                           self.mesh, leaf_spec_tp)

    def act_specs(self):
        dp = self.dp
        # NOTE: no bshd/bskv/bshp constraints — attention/ssm head sharding
        # propagates from the column-sharded projections (Megatron layout);
        # forcing a different layout mid-layer makes GSPMD insert
        # catastrophic reshard-replicate copies (measured: 44 GB/layer).
        return {
            "btd": PS(dp, None, None),
            "btf": PS(dp, None, "model"),
            "btv": PS(dp, None, "model"),
            "head_w": PS("model", None),           # lm-head grad (V,d)
            "becd": PS(dp, "model", None, None),   # MoE expert-sharded
            "becf": PS(dp, "model", None, None),
            "btd_dec": PS(dp, None, None),
        }


class Fsdp(Strategy):
    name = "fsdp"

    @property
    def batch_axes(self):
        # weights are gathered per block -> compute is pure DP over every
        # chip: batch shards over (pod, data, model)
        return self.dp + ("model",)

    def param_specs(self, model):
        return _tree_specs(model.param_axes(), model.abstract_params(),
                           self.mesh, leaf_spec_fsdp)

    def act_specs(self):
        bd = self.batch_axes
        return {
            "btd": PS(bd, None, None),
            "btf": PS(bd, None, None),
            "btv": PS(bd, None, None),
            "bshd": PS(bd, None, None, None),
            "bskv": PS(bd, None, None, None),
            "bshp": PS(bd, None, None, None),
            "becd": PS(bd, None, None, None),
            "becf": PS(bd, None, None, None),
            "btd_dec": PS(bd, None, None),
        }


class TpServe(Strategy):
    name = "tp_serve"

    def __init__(self, mesh, weight_gathered: bool = False):
        super().__init__(mesh)
        self.weight_gathered = weight_gathered

    def param_specs(self, model):
        if self.weight_gathered:
            return _tree_specs(model.param_axes(), model.abstract_params(),
                               self.mesh, leaf_spec_fsdp)
        return _tree_specs(model.param_axes(), model.abstract_params(),
                           self.mesh, leaf_spec_tp)

    def cache_specs(self, cache_abstract, batch: int) -> Any:
        """Stacked caches are (L, B, S, ...): batch over dp when divisible,
        KV sequence chunks over 'model' (flash-decoding combine). When the
        batch cannot shard (e.g. long_500k B=1), the sequence dim spreads
        over ('data','model') instead so all chips hold cache shards."""
        dp = self.dp
        mesh = self.mesh
        dpn = int(np.prod([mesh.shape[a] for a in dp]))

        def leaf(x):
            shape = x.shape
            # stacked layout: (L, B, S, ...); per-layer layout: (B, S, ...)
            if len(shape) >= 2 and shape[1] == batch:
                bdim = 1
            elif len(shape) >= 1 and shape and shape[0] == batch:
                bdim = 0
            else:
                return PS()
            sdim = bdim + 1
            spec = [None] * len(shape)
            batch_ok = batch % dpn == 0
            if batch_ok:
                spec[bdim] = dp
            if len(shape) >= sdim + 2 and shape[sdim] >= 1024:
                if batch_ok and shape[sdim] % mesh.shape["model"] == 0:
                    spec[sdim] = "model"
                elif not batch_ok:
                    full = dp + ("model",)
                    n = int(np.prod([mesh.shape[a] for a in full]))
                    if shape[sdim] % n == 0:
                        spec[sdim] = full
                    elif shape[sdim] % mesh.shape["model"] == 0:
                        spec[sdim] = "model"
            return PS(*spec)
        return jax.tree_util.tree_map(leaf, cache_abstract)

    def paged_cache_specs(self, cache_abstract, batch: int) -> Any:
        """Paged-pool analogue of cache_specs: the page dimension of each
        (L, P, page, Hkv, D) pool chunks over 'model' (pages play the
        dense layout's sequence-shard role — kernels/sharded.py's
        paged combine), page tables/indices shard over dp with the slot
        batch when divisible."""
        mesh = self.mesh
        dp = self.dp
        dpn = int(np.prod([mesh.shape[a] for a in dp]))
        batch_ok = batch % dpn == 0

        def leaf(x):
            shape = x.shape
            if len(shape) == 5:            # stacked pool (L,P,page,Hkv,D)
                pages, ps = shape[1], shape[2]
                if pages * ps >= 1024 and pages % mesh.shape["model"] == 0:
                    return PS(None, "model", None, None, None)
                return PS()
            if len(shape) >= 2 and shape[1] == batch:   # (L,B[,M]) pt/idx
                return PS(None, dp if batch_ok else None,
                          *([None] * (len(shape) - 2)))
            return PS()
        return jax.tree_util.tree_map(leaf, cache_abstract)

    def act_specs(self):
        dp = self.dp
        return {
            "btd": PS(dp, None, None),
            "btf": PS(dp, None, "model"),
            "btv": PS(dp, None, "model"),
            "head_w": PS("model", None),
            "bshd": PS(dp, None, None, None),
            "bskv": PS(dp, None, None, None),
            "bshp": PS(dp, None, None, None),
            "becd": PS(dp, "model", None, None),
            "becf": PS(dp, "model", None, None),
            "btd_dec": PS(dp, None, None),
        }


def make_strategy(name: str, mesh, **kw) -> Strategy:
    return {"dp_tp": DpTp, "fsdp": Fsdp, "tp_serve": TpServe}[name](mesh, **kw)

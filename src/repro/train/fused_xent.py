"""Vocab-parallel fused LM-head + softmax cross-entropy.

Megatron-style: under ``shard_map`` each device computes only its vocab
shard of the logits (never materialized globally, never in f32 globally),
exchanges two (B,S) rowwise statistics (pmax / psum), and the custom vjp
computes dx/dw with shard-local einsums + small psums.

This exists because GSPMD's default plan for the head-matmul backward
all-gathers the full (B,S,V) cotangent (~40 GB/device at qwen3-14b scale).
Fallback: a plain (constrained) implementation when no mesh is active or
the vocab does not divide the model axis.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _plain(x, w, labels, z_loss):
    from repro.sharding.ctx import shard
    logits = shard(jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype)), "btv")
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    onehot = shard(jax.nn.one_hot(labels, lf.shape[-1], dtype=jnp.bfloat16),
                   "btv")
    ll = jnp.einsum("bsv,bsv->bs", lf, onehot,
                    preferred_element_type=jnp.float32)
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss


def make_fused_xent(mesh, dp_axes: Tuple[str, ...], z_loss: float = 0.0):
    """Returns loss_fn(x, w, labels) -> scalar.

    x: (B,S,d) compute dtype; w: (V,d) param head (vocab-major);
    labels: (B,S) int32.  V must divide the 'model' axis.
    """
    model_ax = "model"
    tp = mesh.shape[model_ax]

    x_spec = PS(dp_axes, None, None)
    w_spec = PS(model_ax, None)
    l_spec = PS(dp_axes, None)

    @jax.custom_vjp
    def fused(x, w, labels):
        return _fwd_value(x, w, labels)

    def _local_fwd(x_l, w_l, lab_l):
        f32 = jnp.float32
        logits = jnp.einsum("bsd,vd->bsv", x_l, w_l.astype(x_l.dtype),
                            preferred_element_type=f32)  # (b,s,v/tp) f32
        m_l = jnp.max(logits, axis=-1)
        m = jax.lax.pmax(m_l, model_ax)                   # (b,s)
        se = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                          model_ax)
        lse = jnp.log(se) + m                             # (b,s)
        v_l = w_l.shape[0]
        v_off = jax.lax.axis_index(model_ax) * v_l
        local_lab = lab_l - v_off
        in_shard = (local_lab >= 0) & (local_lab < v_l)
        idx = jnp.clip(local_lab, 0, v_l - 1)
        ll_l = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_shard, ll_l, 0.0), model_ax)
        return logits, lse, ll

    def _fwd_value(x, w, labels):
        def f(x_l, w_l, lab_l):
            _, lse, ll = _local_fwd(x_l, w_l, lab_l)
            ntok = np.prod(lab_l.shape)
            loss = jnp.sum(lse - ll) / ntok
            if z_loss:
                loss = loss + z_loss * jnp.sum(jnp.square(lse)) / ntok
            return jax.lax.pmean(loss, dp_axes)           # replicated scalar
        return shard_map(f, mesh, (x_spec, w_spec, l_spec), PS())(
            x, w, labels)

    def _fwd_rule(x, w, labels):
        return _fwd_value(x, w, labels), (x, w, labels)

    def _bwd_rule(res, g):
        x, w, labels = res

        def f(x_l, w_l, lab_l):
            f32 = jnp.float32
            logits, lse, ll = _local_fwd(x_l, w_l, lab_l)
            p = jnp.exp(logits - lse[..., None])          # softmax local
            v_l = w_l.shape[0]
            v_off = jax.lax.axis_index(model_ax) * v_l
            local_lab = lab_l - v_off
            in_shard = (local_lab >= 0) & (local_lab < v_l)
            onehot_val = jnp.where(in_shard, 1.0, 0.0)
            idx = jnp.clip(local_lab, 0, v_l - 1)
            if z_loss:
                scale = (1.0 + 2.0 * z_loss * lse)[..., None]
            else:
                scale = 1.0
            ntok_global = np.prod(lab_l.shape) * np.prod(
                [mesh.shape[a] for a in dp_axes])
            dl = p * scale
            # subtract onehot at the label slot (only in its shard)
            upd = -onehot_val
            dl = dl.at[jnp.arange(dl.shape[0])[:, None],
                       jnp.arange(dl.shape[1])[None, :], idx].add(upd)
            dl = dl * (g / ntok_global)
            dl = dl.astype(x_l.dtype)
            dx_l = jax.lax.psum(
                jnp.einsum("bsv,vd->bsd", dl, w_l.astype(dl.dtype)), model_ax)
            dw_l = jax.lax.psum(
                jnp.einsum("bsv,bsd->vd", dl, x_l), dp_axes)
            return dx_l.astype(x_l.dtype), dw_l.astype(w.dtype)

        dx, dw = shard_map(f, mesh, (x_spec, w_spec, l_spec),
                           (x_spec, w_spec))(x, w, labels)
        dlab = np.zeros(labels.shape, jax.dtypes.float0)
        return dx, dw, dlab

    fused.defvjp(_fwd_rule, _bwd_rule)
    return fused


def lm_loss(x, w, labels, *, z_loss: float = 0.0, sharder=None):
    """Dispatch: fused vocab-parallel path when a mesh is active, 'model' is
    free (not carrying batch), and the padded vocab divides it; plain
    constrained path otherwise (e.g. fsdp, where batch covers every axis and
    per-device logits are small)."""
    if sharder is not None and "model" in sharder.mesh.shape:
        V = w.shape[0]
        mesh = sharder.mesh
        dp = sharder.batch_axes
        if ("model" not in dp and V % mesh.shape["model"] == 0):
            B, S = labels.shape
            dpn = int(np.prod([mesh.shape[a] for a in dp]))
            if B % dpn == 0:
                fused = make_fused_xent(mesh, dp, z_loss)
                return fused(x, w, labels)
    return _plain(x, w, labels, z_loss)

"""Blocked causal flash attention for TPU (Pallas).

TPU adaptation notes (vs. the canonical CUDA flash-attention):
  * tiles are BlockSpec'd into VMEM; the (Bq x D) @ (D x Bk) products map
    onto the 128x128 MXU, so block sizes are multiples of 128 where the
    head dim allows;
  * the kv-block loop is the innermost grid dimension; running max /
    denominator / accumulator live in VMEM scratch that persists across the
    innermost grid iterations ("arbitrary" dimension semantics), which is
    the TPU-idiomatic replacement for a CUDA thread-block software loop;
  * GQA is handled in the index_map (q head h reads kv head h // G), so
    no KV replication is materialized in HBM.

Differentiable through a ``custom_vjp`` whose backward is the pure-JAX
recompute rule of ``flash_xla`` (no Pallas backward kernel).

Validated in interpret mode on CPU against ``ref.attention_ref``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import flash_xla

NEG_INF = -1e30


def online_softmax_update(s, v, m_prev, l_prev, acc_prev):
    """One flash accumulation step on values, shared by every attention
    kernel in this package (causal flash, paged decode, paged window).

    ``s``: (rows, cols) masked f32 scores; ``v``: (cols, D) f32 values;
    ``m_prev``/``l_prev``: (rows, 1) running max / denominator;
    ``acc_prev``: (rows, D) output accumulator. Returns the updated
    ``(m, l, acc)``."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  seq_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    run = True
    if causal:
        # skip fully-masked kv blocks (strictly above the diagonal)
        run = (k_start <= q_start + block_q - 1)

    @pl.when(run if causal else (ki >= 0))
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (block_q, D)
        k = k_ref[0].astype(jnp.float32)            # (block_k, D)
        v = v_ref[0].astype(jnp.float32)            # (block_k, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                # (block_q, block_k)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_len
        if causal:
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)
        m_scr[...], l_scr[...], acc_scr[...] = online_softmax_update(
            s, v, m_scr[...], l_scr[...], acc_scr[...])

    @pl.when(ki == nk - 1)
    def _fin():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        lse_ref[0] = m_scr[...] + jnp.log(l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, Hq, D), k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    Differentiable: the backward is ``flash_xla``'s recompute rule over
    the (q, k, v, out, lse) residuals this forward saves."""
    return _flash(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, dout):
    return flash_xla._bwd_rule(causal, 0, flash_xla.DEFAULT_CHUNK, res, dout)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    """Pallas forward: (out (B, Sq, Hq, D), lse (B, Sq, Hkv, G)) — lse in
    ``flash_xla``'s residual layout."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    # (B, H, S, D) layout for clean 2D blocks per (b, h) program.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))
    # pad seq to block multiples
    Sq_p = pl.cdiv(Sq, block_q) * block_q
    Skv_p = pl.cdiv(Skv, block_k) * block_k
    if Sq_p != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Skv_p != Skv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))

    grid = (B * Hq, Sq_p // block_q, Skv_p // block_k)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        h = bh % Hq
        b = bh // Hq
        return (b * Hkv + h // G, ki, 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=[pl.BlockSpec((1, block_q, D), q_index),
                   pl.BlockSpec((1, block_q, 1), q_index)],
        out_shape=[jax.ShapeDtypeStruct((B * Hq, Sq_p, D), q.dtype),
                   jax.ShapeDtypeStruct((B * Hq, Sq_p, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt.reshape(B * Hq, Sq_p, D), kt.reshape(B * Hkv, Skv_p, D),
      vt.reshape(B * Hkv, Skv_p, D))

    out = out.reshape(B, Hq, Sq_p, D)[:, :, :Sq].transpose(0, 2, 1, 3)
    lse = lse.reshape(B, Hq, Sq_p)[:, :, :Sq].transpose(0, 2, 1)
    return out, lse.reshape(B, Sq, Hkv, G)

"""Paged-attention decode kernel (Pallas TPU).

One new token per slot attends over its whole paged KV history. The
kernel gathers K/V pages from the pool *inside* the kernel: the page
table and per-slot positions are scalar-prefetched, and each kv grid
step's BlockSpec index map chases ``pt[b, m]`` directly, so the
(B, M*page) logical view the ref path materializes in HBM
(``ref.paged_gather``) never exists. The new token's K/V row is spliced
into its page block in VMEM (the pool scatter itself stays a cheap
O(B*Hkv*D) ``ref.paged_update`` outside the kernel — one row per slot).

Waste counters (the machine-code tier of the detector stack, see
DESIGN.md § Kernel tier): at the splice step — the store site of the
new K/V row — the kernel compares the incoming row against the pool
content it overwrites with ``core.events.silent_mask`` semantics and
emits per-slot element counts [stored, silent, dropped]:

  * stored  — elements whose page-table-mapped store will land;
  * silent  — stored elements equal (within tol) to the old value
              (paper Def. 2 silent stores, counted at the store site);
  * dropped — elements whose target page is unmapped (the store is
              masked off: dead lanes).

Grid iteration order is (B, M) with the page dim innermost; one grid
step covers every head of a slot, so each block's last two dims are the
full (Hkv, D) / (G, D) head extents the TPU tiling accepts. Flash
accumulators live in VMEM scratch across the page sweep; both grid dims
are "arbitrary" (scratch carries state), so revisiting semantics match
interpret mode.

Validated in interpret mode on CPU against the ref composition
``paged_update -> paged_gather -> attention_ref``, and on a TPU by
``chip_smoke.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.events import silent_mask
from repro.kernels.flash_attention import NEG_INF, online_softmax_update


def counter_row(stored, silent, dropped):
    """(1, 1, 3) int32 [stored, silent, dropped] block from three scalars
    (vector selects: the TPU has no scalar stores into VMEM)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 3), 2)
    return jnp.where(lane == 0, stored, jnp.where(lane == 1, silent, dropped))


def _decode_kernel(pt_ref, idx_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                   o_ref, lse_ref, cnt_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, ps: int, Hkv: int, tol: float):
    b = pl.program_id(0)
    m = pl.program_id(1)
    nm = pl.num_programs(1)
    idx = idx_ref[b]
    page = pt_ref[b, m]

    @pl.when(m == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    pos = m * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
    is_new = pos == idx                                   # (ps, 1)

    @pl.when((idx >= 0) & (page >= 0) & (m * ps <= idx))
    def _attend():
        for g in range(Hkv):
            q = q_ref[0, g].astype(jnp.float32)           # (G, D)
            k = jnp.where(is_new, kn_ref[0, g:g + 1].astype(jnp.float32),
                          k_ref[0, :, g].astype(jnp.float32))   # (ps, D)
            v = jnp.where(is_new, vn_ref[0, g:g + 1].astype(jnp.float32),
                          v_ref[0, :, g].astype(jnp.float32))
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(pos.T <= idx, s * scale, NEG_INF)     # (G, ps)
            m_scr[g], l_scr[g], acc_scr[g] = online_softmax_update(
                s, v, m_scr[g], l_scr[g], acc_scr[g])

    # --- store-site counters: the new row lands in page idx // ps ------
    # (a target past the table is counted at the last step, as dropped)
    tgt = idx // ps

    @pl.when((idx >= 0) & ((m == tgt) | ((m == nm - 1) & (tgt >= nm))))
    def _count():
        D = k_ref.shape[-1]
        sil = jnp.zeros((), jnp.int32)
        for g in range(Hkv):
            for new_ref, old_ref in ((kn_ref, k_ref), (vn_ref, v_ref)):
                old = old_ref[0, :, g].astype(jnp.float32)   # pre-store
                new = new_ref[0, g:g + 1].astype(jnp.float32)
                hit = silent_mask(old, new, tol) & is_new
                sil += jnp.sum(jnp.where(hit, 1, 0), dtype=jnp.int32)
        ok = (tgt < nm) & (page >= 0)
        full = 2 * Hkv * D
        cnt_ref[...] = counter_row(jnp.where(ok, full, 0),
                                   jnp.where(ok, sil, 0),
                                   jnp.where(ok, 0, full))

    @pl.when(m == nm - 1)
    def _fin():
        l = l_scr[...]
        lse_ref[0] = jnp.where(l > 0.0, m_scr[...] + jnp.log(
            jnp.where(l > 0.0, l, 1.0)), NEG_INF)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           pool_k: jax.Array, pool_v: jax.Array,
                           pt: jax.Array, idx: jax.Array, *,
                           tol: float = 0.0,
                           interpret: bool = False):
    """q/k_new/v_new: (B, 1, H*, D); pool: (P, page, Hkv, D); pt: (B, M);
    idx: (B,) per-slot positions (negative = idle slot, attends nothing).

    Returns ``(out, lse, counters)``: out (B, 1, Hq, D); lse (B, Hq)
    per-(slot, head) log-sum-exp for sharded flash combines (NEG_INF
    where nothing was attended); counters (B, 3) int32 — see module doc.

    NOTE: the kernel does not write the pool. Callers scatter the single
    new row with ``ref.paged_update`` (the counters still describe that
    store: they are measured here against pre-store pool content).
    """
    B, S, Hq, D = q.shape
    assert S == 1, "decode kernel is single-token"
    P, ps, Hkv, _ = pool_k.shape
    M = pt.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    pt = pt.astype(jnp.int32)
    idx = idx.astype(jnp.int32)
    q4 = q.reshape(B, Hkv, G, D)
    # round-trip the new row through the pool dtype: the ref path attends
    # the value the pool actually stores, so the splice must match it bit
    # for bit (e.g. bf16 pools under f32 activations)
    pdt = pool_k.dtype
    kn = k_new.reshape(B, Hkv, D).astype(pdt)
    vn = v_new.reshape(B, Hkv, D).astype(pdt)

    def slot_index(b, m, pt_ref, idx_ref):
        return (b, 0, 0, 0)

    def new_index(b, m, pt_ref, idx_ref):
        return (b, 0, 0)

    def pool_index(b, m, pt_ref, idx_ref):
        return (jnp.clip(pt_ref[b, m], 0, P - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), slot_index),
            pl.BlockSpec((1, Hkv, D), new_index),
            pl.BlockSpec((1, Hkv, D), new_index),
            pl.BlockSpec((1, ps, Hkv, D), pool_index),
            pl.BlockSpec((1, ps, Hkv, D), pool_index),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), slot_index),
            pl.BlockSpec((1, Hkv, G, 1), slot_index),
            pl.BlockSpec((1, 1, 3), new_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),      # running denom
            pltpu.VMEM((Hkv, G, D), jnp.float32),      # accumulator
        ],
    )
    out, lse, cnt = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, ps=ps, Hkv=Hkv,
                          tol=tol),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 3), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(pt, idx, q4, kn, vn, pool_k, pool_v)
    return out.reshape(B, 1, Hq, D), lse.reshape(B, Hq), cnt.reshape(B, 3)

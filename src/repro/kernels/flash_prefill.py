"""Fused paged window-attention kernel (Pallas TPU): causal flash
prefill over a paged pool, and width-(k+1) speculative verify.

One kernel serves both serving forwards that push an S-token *window*
at per-slot offsets ``idx`` against a paged KV pool:

  * prefill (``LM.prefill``) — S prompt tokens whose K/V rows are stored
    into the page pool;
  * verify (``LM.verify``) — S = k+1 draft tokens; ``store=True`` is
    spec="overwrite" (all rows stored, rejected rows become dead
    stores), ``store=False`` is spec="defer" (rollback: pool untouched,
    the kernel only computes the spliced-window attention).

The committed history is gathered from the pool *inside* the kernel via
the scalar-prefetched page table (no ``paged_gather`` materialization);
the window K/V ride in a separate operand. One grid step covers every
head of a slot, so each block's last two dims are the full (Hkv, D)
head extents the TPU tiling accepts. The innermost grid dim runs ``M``
committed-page steps, one window step, then (store mode) ``Wc``
store-site steps over the pages the window lands in.

Waste counters ([stored, silent, dropped] per slot, see
``kernels/paged_attention.py`` and DESIGN.md § Kernel tier) are
measured at those store-site steps, by comparing each page tile against
the window rows about to overwrite it with ``core.events.silent_mask``
semantics. The kernel itself never writes the pool: a Pallas TPU output
block is not loaded from HBM, so a read-modify-write epilogue through
aliased outputs would store stale VMEM over the pages it did not mean
to touch. The rows land instead through one ``ref.paged_update``
scatter after the kernel (O(B*S*Hkv*D), the rows the counters
describe), which is the same page-table routing the decode kernel uses.
The COW invariant of `serve/kv_cache.py` (a page being extended is
exclusively mapped; shared pages are read-only) keeps the per-slot
stores race-free.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.events import silent_mask
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import NEG_INF, online_softmax_update
from repro.kernels.paged_attention import counter_row


def _window_kernel(pt_ref, idx_ref, q_ref, kw_ref, vw_ref, wv_ref,
                   k_ref, v_ref,
                   o_ref, lse_ref, cnt_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, ps: int, Hkv: int, G: int, S: int, M: int,
                   block_q: int, tol: float):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    mi = pl.program_id(2)
    idx = idx_ref[b]
    rows = G * block_q                       # query rows of one kv head

    @pl.when((qi == 0) & (mi == 0))
    def _zero_cnt():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(mi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(k_of, v_of, mask):
        """Flash step of every kv head's G query heads against (cols, D)
        keys/values; ``mask``: (rows, cols) attendable positions."""
        for g in range(Hkv):
            q = q_ref[0, g * G:(g + 1) * G].astype(jnp.float32)
            q = q.reshape(rows, q.shape[-1])              # (G*bq, D)
            k = k_of(g).astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s * scale, NEG_INF)
            r = pl.ds(g * rows, rows)
            m_scr[r], l_scr[r], acc_scr[r] = online_softmax_update(
                s, v_of(g).astype(jnp.float32), m_scr[r], l_scr[r],
                acc_scr[r])

    # ---- committed-history page steps -------------------------------
    page = pt_ref[b, jnp.clip(mi, 0, M - 1)]

    @pl.when((mi < M) & (idx >= 1) & (page >= 0) & (mi * ps < idx))
    def _attend_page():
        kpos = mi * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        attend(lambda g: k_ref[0, :, g], lambda g: v_ref[0, :, g],
               kpos < idx)

    # ---- window step: in-window causal attention --------------------
    @pl.when((mi == M) & (idx >= 0))
    def _attend_window():
        shape = (G, block_q, S)
        r = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        c = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        mask = ((c <= r) & (wv_ref[0] > 0)).reshape(rows, S)
        attend(lambda g: kw_ref[0, pl.ds(ps, S), g],
               lambda g: vw_ref[0, pl.ds(ps, S), g], mask)

    @pl.when(mi == M)
    def _fin():
        l = l_scr[...]
        lse = jnp.where(l > 0.0, m_scr[...] + jnp.log(
            jnp.where(l > 0.0, l, 1.0)), NEG_INF)
        lse_ref[0] = lse.reshape(lse_ref.shape[1:])
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).reshape(o_ref.shape[1:]).astype(
            o_ref.dtype)

    # ---- store-site steps: count the window rows landing in each page --
    # (store mode only; the grid has no such steps otherwise). Page j of
    # the window holds window rows [page_i*ps - idx, +ps), a contiguous
    # range of the ps-row-padded window operand.
    @pl.when((mi > M) & (qi == 0) & (idx >= 0))
    def _count():
        page_i = jnp.maximum(idx, 0) // ps + (mi - (M + 1))
        entry = pt_ref[b, jnp.clip(page_i, 0, M - 1)]
        page_ok = (page_i < M) & (entry >= 0)
        start = page_i * ps - idx                 # window row at offset 0
        sw = start + jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
        sel = (sw >= 0) & (sw < S)                # (ps, 1)
        lo = jnp.clip(start, -ps, S) + ps
        D = k_ref.shape[-1]
        n_sel = jnp.sum(jnp.where(sel, 1, 0), dtype=jnp.int32)
        sil = jnp.zeros((), jnp.int32)
        for g in range(Hkv):
            for w_ref, p_ref in ((kw_ref, k_ref), (vw_ref, v_ref)):
                new = w_ref[0, pl.ds(lo, ps), g].astype(jnp.float32)
                old = p_ref[0, :, g].astype(jnp.float32)   # pre-store
                hit = silent_mask(old, new, tol) & sel
                sil += jnp.sum(jnp.where(hit, 1, 0), dtype=jnp.int32)
        n = 2 * Hkv * D * n_sel
        cnt_ref[...] += counter_row(jnp.where(page_ok, n, 0),
                                    jnp.where(page_ok, sil, 0),
                                    jnp.where(page_ok, 0, n))


def paged_window_attention(q: jax.Array, k_win: jax.Array, v_win: jax.Array,
                           pool_k: jax.Array, pool_v: jax.Array,
                           pt: jax.Array, idx: jax.Array, *,
                           store: bool = True,
                           block_q: int = 128,
                           tol: float = 0.0,
                           interpret: bool = False):
    """q: (B, S, Hq, D) at per-slot offsets idx (B,); k_win/v_win:
    (B, S, Hkv, D); pool: (P, page, Hkv, D); pt: (B, M).

    Returns ``(out, lse, counters, new_pool_k, new_pool_v)``: out
    (B, S, Hq, D); lse (B, Hq, S); counters (B, 3) int32. With
    ``store=False`` the pools come back unchanged and the counters are
    zero. Matches the ref compositions used by
    ``models.layers.apply_attention``: ``paged_update -> paged_gather ->
    attention_ref`` for store mode, the spliced-gather "defer" path
    otherwise. Idle slots (idx < 0) attend nothing and come back zero
    (the ref path yields NaN there; the engine discards both).
    """
    B, S, Hq, D = q.shape
    P, ps, Hkv, _ = pool_k.shape
    M = pt.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    pdt = pool_k.dtype

    pt = pt.astype(jnp.int32)
    idx = idx.astype(jnp.int32)

    # window validity per mode (page-table reads only — O(B*S) scalars)
    gpos = jnp.maximum(idx, 0)[:, None] + jnp.arange(S)[None, :]   # (B, S)
    if store:
        pg = jnp.floor_divide(gpos, ps)
        entry = jnp.where(pg < M,
                          jnp.take_along_axis(pt, jnp.clip(pg, 0, M - 1),
                                              axis=1), -1)
        wv = (entry >= 0).astype(jnp.int32)
    else:
        wv = (gpos < M * ps).astype(jnp.int32)
    wv = wv[:, None, :]                                      # (B, 1, S)

    block_q = min(block_q, max(S, 8))
    Sq_p = pl.cdiv(S, block_q) * block_q
    qt = q.transpose(0, 2, 1, 3)                        # (B, Hq, S, D)
    if Sq_p != S:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sq_p - S), (0, 0)))
    nq = Sq_p // block_q

    # window rows in the pool dtype (the values a store lands), padded by
    # one page on both sides for the store-site steps' page-aligned reads
    pad = ((0, 0), (ps, ps), (0, 0), (0, 0))
    kw = jnp.pad(k_win.astype(pdt), pad)
    vw = jnp.pad(v_win.astype(pdt), pad)

    Wc = pl.cdiv(S, ps) + 1 if store else 0
    grid = (B, nq, M + 1 + Wc)

    def q_index(b, qi, mi, *_):
        return (b, 0, qi, 0)

    def slot_index(b, qi, mi, *_):
        return (b, 0, 0, 0)

    def wv_index(b, qi, mi, *_):
        return (b, 0, 0)

    def pool_index(b, qi, mi, pt_ref, idx_ref):
        w0 = jnp.maximum(idx_ref[b], 0) // ps
        page_i = jnp.where(mi < M, mi, jnp.clip(w0 + mi - M - 1, 0, M - 1))
        return (jnp.clip(pt_ref[b, page_i], 0, P - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hq, block_q, D), q_index),
            pl.BlockSpec((1, S + 2 * ps, Hkv, D), slot_index),
            pl.BlockSpec((1, S + 2 * ps, Hkv, D), slot_index),
            pl.BlockSpec((1, 1, S), wv_index),
            pl.BlockSpec((1, ps, Hkv, D), pool_index),
            pl.BlockSpec((1, ps, Hkv, D), pool_index),
        ],
        out_specs=[
            pl.BlockSpec((1, Hq, block_q, D), q_index),
            pl.BlockSpec((1, Hq, block_q, 1), q_index),
            pl.BlockSpec((1, 1, 3), wv_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hq * block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((Hq * block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hq * block_q, D), jnp.float32),   # accumulator
        ],
    )
    out, lse, cnt = pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, ps=ps, Hkv=Hkv, G=G,
                          S=S, M=M, block_q=block_q, tol=tol),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, 3), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="paged_window_attention",
    )(pt, idx, qt, kw, vw, wv, pool_k, pool_v)

    if store:
        npk, npv = _ref.paged_update(pool_k, pool_v, k_win, v_win, pt, idx)
    else:
        npk, npv = pool_k, pool_v
    out = out[:, :, :S].transpose(0, 2, 1, 3)           # (B, S, Hq, D)
    lse = lse[:, :, :S, 0]
    return out, lse, cnt.reshape(B, 3), npk, npv

"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references (kernel tests assert allclose against
them) AND the default compute path on non-TPU backends — XLA fuses them
well and GSPMD partitions them automatically.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------
# Attention (GQA, causal, optional decode length-mask)
# ----------------------------------------------------------------------
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, q_offset=0,
                  kv_len: Optional[jax.Array] = None,
                  kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,Sq,Hq,D), k/v: (B,Skv,Hkv,D) -> (B,Sq,Hq,D). f32 accumulate.

    ``q_offset``/``kv_len`` may be scalars (one decode position for the
    whole batch) or (B,) vectors (per-slot positions — the serving
    engine's continuous-batching cache, where every row sits at its own
    sequence offset). ``kv_valid`` is an optional (B,Skv) gather-validity
    mask: positions of a paged cache's logical view whose page table
    entry is unmapped (see ``paged_gather``) are masked out like
    positions past ``kv_len``.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / jnp.sqrt(D).astype(jnp.float32))

    mask = None
    if causal:
        if isinstance(q_offset, int) and q_offset == 0:
            # training/prefill-from-scratch call: adding the static 0
            # offset would emit a full-(Sq,) identity add against literal
            # 0 (tier-0 silent_store, ref.py) — same (Sq,) qpos either way
            qpos = jnp.arange(Sq)
        else:
            qpos = jnp.asarray(q_offset)[..., None] + jnp.arange(Sq)
        mask = qpos[..., :, None] >= jnp.arange(Skv)   # (Sq,Skv) | (B,Sq,Skv)
    if kv_len is not None:
        lmask = jnp.arange(Skv) < jnp.asarray(kv_len)[..., None]
        lmask = lmask[..., None, :]              # (1,Skv) | (B,1,Skv)
        mask = lmask if mask is None else (mask & lmask)
    if kv_valid is not None:
        vmask = kv_valid[:, None, :]             # (B,1,Skv)
        mask = vmask if mask is None else (mask & vmask)
    if mask is not None:
        bmask = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        scores = jnp.where(bmask, scores, -jnp.inf)

    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, D)


# ----------------------------------------------------------------------
# Paged KV cache: page-table scatter (store) and gather (load) between
# the logical per-slot view and the flat page pool (serve/kv_cache.py).
# ----------------------------------------------------------------------
def _layer_base(pool: jax.Array, layer: Optional[jax.Array]):
    """Flat row index of ``layer``'s first pool row: a pool is
    (P, page, Hkv, D), or layer-stacked (L, P, page, Hkv, D) with
    ``layer`` naming the layer addressed (0 for an unstacked pool).
    Reads and writes of a stacked pool index its flat rows,
    ``pool.reshape(-1, Hkv, D)``, so no layer is ever sliced out."""
    if layer is None:
        return 0
    return layer * (pool.shape[-4] * pool.shape[-3])


def _page_of(pos: jax.Array, pt: jax.Array, ps: int) -> jax.Array:
    """Pool page of each logical position (-1 where unmapped or
    outside the table)."""
    M = pt.shape[1]
    page_i = jnp.floor_divide(pos, ps)
    return jnp.where(
        (page_i >= 0) & (page_i < M),
        jnp.take_along_axis(pt, jnp.clip(page_i, 0, M - 1), axis=1), -1)


def paged_update(pool_k: jax.Array, pool_v: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, pt: jax.Array,
                 idx: jax.Array,
                 length: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None) -> tuple:
    """Scatter new K/V rows into a paged pool through the page table.

    pool: (P, page, Hkv, D), or (L, P, page, Hkv, D) with ``layer`` the
    layer written (the decode scan's loop-carried pool: the rows land
    in place at [layer, page, offset]); k_new/v_new: (B, S, Hkv, D);
    pt: (B, M) page table (-1 = unmapped); idx: (B,) per-slot write
    positions. Row (b, s) lands at logical position idx[b]+s -> page
    pt[b, pos//page].
    Stores whose position is negative (engine idle-slot sentinel) or
    whose page is unmapped are DROPPED — idle/finished slots write
    nothing past their page-table extent, which is exactly the dead/
    silent-store waste of the dense layout eliminated.

    ``length`` (optional, (B,)): per-slot row budget — rows s >=
    length[b] are dropped too. Speculative rollback commits exactly the
    accepted prefix of a verify window this way (LM.commit_verify), so
    rejected draft rows never reach the pool at all.
    """
    ps = pool_k.shape[-3]
    S = k_new.shape[1]
    pos = idx[:, None] + jnp.arange(S)[None, :]            # (B,S) logical
    if length is not None:
        pos = jnp.where(jnp.arange(S)[None, :] < length[:, None], pos, -1)
    page = _page_of(pos, pt, ps)
    flat = jnp.where((page >= 0) & (pos >= 0),
                     _layer_base(pool_k, layer) + page * ps
                     + jnp.remainder(pos, ps), math.prod(pool_k.shape[:-2]))

    def scat(pool, new):
        rows = pool.reshape((-1,) + pool.shape[-2:])
        rows = rows.at[flat].set(new.astype(pool.dtype), mode="drop")
        return rows.reshape(pool.shape)
    return scat(pool_k, k_new), scat(pool_v, v_new)


def paged_store_counts(pool_k: jax.Array, pool_v: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       pt: jax.Array, idx: jax.Array,
                       length: Optional[jax.Array] = None,
                       tol: float = 0.0,
                       layer: Optional[jax.Array] = None) -> jax.Array:
    """Waste counters for a ``paged_update`` store, per slot: (B, 3) int32
    ``[stored, silent, dropped]`` element counts over K and V (pools and
    ``layer`` as in ``paged_update``).

    This is the pure-jnp oracle for the in-kernel store-site counters
    (kernel tier, see DESIGN.md): *stored* elements land through the
    page table; *silent* stored elements equal the pool content they
    overwrite within ``core.events.silent_mask`` tolerance (paper Def. 2,
    after the round-trip through the pool dtype); *dropped* elements
    target an unmapped page and are masked off (dead store lanes). Idle
    slots (negative positions) attempt no store and count nothing.
    """
    from repro.core.events import silent_mask
    ps = pool_k.shape[-3]
    B, S, Hkv, D = k_new.shape
    pos = idx[:, None] + jnp.arange(S)[None, :]            # (B, S)
    attempted = pos >= 0
    if length is not None:
        attempted = attempted & (jnp.arange(S)[None, :] < length[:, None])
    page = _page_of(pos, pt, ps)
    landing = attempted & (page >= 0)

    flat = jnp.where(landing, _layer_base(pool_k, layer) + page * ps
                     + jnp.remainder(pos, ps), 0)

    def row_silent(pool, new):
        old = pool.reshape((-1,) + pool.shape[-2:])[flat]   # (B,S,Hkv,D)
        oldf = old.astype(jnp.float32)
        newf = new.astype(pool.dtype).astype(jnp.float32)
        return jnp.sum(silent_mask(oldf, newf, tol), axis=(2, 3),
                       dtype=jnp.int32)                        # (B, S)

    sil = jnp.where(landing,
                    row_silent(pool_k, k_new) + row_silent(pool_v, v_new), 0)
    stored = jnp.sum(jnp.where(landing, 2 * Hkv * D, 0), axis=1,
                     dtype=jnp.int32)
    silent = jnp.sum(sil, axis=1, dtype=jnp.int32)
    dropped = jnp.sum(jnp.where(attempted & (page < 0), 2 * Hkv * D, 0),
                      axis=1, dtype=jnp.int32)
    return jnp.stack([stored, silent, dropped], axis=1)


def paged_decode_ref(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     pool_k: jax.Array, pool_v: jax.Array,
                     pt: jax.Array, idx: jax.Array,
                     tol: float = 0.0,
                     layer: Optional[jax.Array] = None) -> tuple:
    """Oracle for the paged-attention decode kernel: the store-then-
    gather-then-mask composition the serving fallback path runs, plus
    the store-site waste counters (pools and ``layer`` as in
    ``paged_update``). Returns (out, ck, cv, counters)."""
    dt = q.dtype
    cnt = paged_store_counts(pool_k, pool_v, k_new, v_new, pt, idx, tol=tol,
                             layer=layer)
    ck, cv = paged_update(pool_k, pool_v, k_new, v_new, pt, idx, layer=layer)
    gk, valid = paged_gather(ck, pt, layer)
    gv, _ = paged_gather(cv, pt, layer)
    out = attention_ref(q, gk.astype(dt), gv.astype(dt), causal=True,
                        q_offset=idx, kv_len=idx + 1, kv_valid=valid)
    return out, ck, cv, cnt


def paged_window_ref(q: jax.Array, k_win: jax.Array, v_win: jax.Array,
                     pool_k: jax.Array, pool_v: jax.Array,
                     pt: jax.Array, idx: jax.Array, *,
                     store: bool = True, tol: float = 0.0,
                     layer: Optional[jax.Array] = None) -> tuple:
    """Oracle for the fused paged window kernel (prefill / verify).

    ``store=True`` is the scatter-then-gather composition the overwrite
    paths run (all S window rows stored through the page table, then
    attention over the gathered view); ``store=False`` is the "defer"
    composition (window spliced into the gathered view, pool untouched,
    zero store counters). Pools and ``layer`` as in ``paged_update``.
    Returns (out, ck, cv, counters).
    """
    dt = q.dtype
    B, S = q.shape[:2]
    if store:
        cnt = paged_store_counts(pool_k, pool_v, k_win, v_win, pt, idx,
                                 tol=tol, layer=layer)
        ck, cv = paged_update(pool_k, pool_v, k_win, v_win, pt, idx,
                              layer=layer)
        gk, valid = paged_gather(ck, pt, layer)
        gv, _ = paged_gather(cv, pt, layer)
    else:
        cnt = jnp.zeros((B, 3), jnp.int32)
        ck, cv = pool_k, pool_v
        gk, valid = paged_gather(pool_k, pt, layer)
        gv, _ = paged_gather(pool_v, pt, layer)
        ext = gk.shape[1]
        pos = idx[:, None] + jnp.arange(S)[None, :]
        tgt = jnp.where((pos >= 0) & (pos < ext), pos, ext)
        bidx = jnp.arange(B)[:, None]
        gk = gk.at[bidx, tgt].set(k_win.astype(gk.dtype), mode="drop")
        gv = gv.at[bidx, tgt].set(v_win.astype(gv.dtype), mode="drop")
        valid = valid.at[bidx, tgt].set(True, mode="drop")
    out = attention_ref(q, gk.astype(dt), gv.astype(dt), causal=True,
                        q_offset=idx, kv_len=idx + S, kv_valid=valid)
    return out, ck, cv, cnt


def paged_gather(pool: jax.Array, pt: jax.Array,
                 layer: Optional[jax.Array] = None) -> tuple:
    """Logical per-slot view of a paged pool (or of ``layer`` of a
    stacked one): (B, M*page, ...) plus the (B, M*page) validity mask
    (False where the page table is unmapped — gathered garbage there
    must be masked, see attention_ref.kv_valid).
    """
    P, ps = pool.shape[-4], pool.shape[-3]
    B, M = pt.shape
    pages = pool.reshape((-1,) + pool.shape[-3:])        # (L*P, page, ...)
    first = 0 if layer is None else layer * P
    g = jnp.take(pages, first + jnp.clip(pt, 0, P - 1), axis=0)
    g = g.reshape((B, M * ps) + pool.shape[-2:])
    valid = jnp.repeat(pt >= 0, ps, axis=1)
    return g, valid


# ----------------------------------------------------------------------
# Silent-compare: fraction of "silent" (unchanged) elements between two
# buffers — the detector hot-spot (paper Defs. 2-3 value equality, with
# the paper's FP tolerance semantics; tol=0 => exact).
# ----------------------------------------------------------------------
def silent_compare_ref(a: jax.Array, b: jax.Array, tol: float = 0.01) -> jax.Array:
    """Count elements where b is a 'silent' overwrite of a. Returns int32 count.

    Uses the substrate's single silent-match definition (symmetric relative
    tolerance; NaN padding is never silent)."""
    from repro.core.events import silent_mask
    a = a.astype(jnp.float32).ravel()
    b = b.astype(jnp.float32).ravel()
    return jnp.sum(silent_mask(a, b, tol), dtype=jnp.int32)


# ----------------------------------------------------------------------
# RMSNorm (fused)
# ----------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)

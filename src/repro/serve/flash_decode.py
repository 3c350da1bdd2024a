"""Flash-decoding under shard_map: sequence-chunk-sharded KV cache.

GSPMD's automatic plan for one-token decode against a seq-sharded cache
all-gathers the full K/V per layer (measured: 2 GB/layer/token at qwen3
scale — 56 GB/device/token). The manual plan is textbook flash-decoding:

  * the cache stays sharded in sequence chunks over `seq_axes`;
  * the new token's K/V row is written by the one shard that owns slot
    `idx` (clipped-index DUS — O(1) work, no copies, no gathers);
  * every shard computes partial attention over its chunk with a running
    max/denominator, and partials combine with one tiny pmax+psum.

Works for any head count, any batch, any cache length (incl. 500k), and
is exact (same math as ref.attention_ref).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.train.fused_xent import shard_map


def _axis_index(names: Tuple[str, ...], mesh) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for n in names:
        idx = idx * mesh.shape[n] + jax.lax.axis_index(n)
    return idx


def _pallas_mode() -> Tuple[bool, bool]:
    """(use Pallas kernels inside the shard bodies, interpret mode)."""
    from repro.kernels import ops
    return ops._use_pallas(), ops._pallas_interpret()


def _lse_combine(o_l, lse, seq_axes, out_dtype):
    """Flash-decoding cross-shard combine from per-shard normalized
    outputs + log-sum-exp: out = Σ_i e^{lse_i - max} o_i / Σ_i e^{lse_i
    - max}. Idle slots (all lse = -inf) come back zero, no NaNs.
    o_l: (..., D) with lse broadcastable to o_l.shape[:-1]."""
    gm = jax.lax.pmax(lse, seq_axes)
    w = jnp.exp(lse - gm)
    den = jax.lax.psum(w, seq_axes)
    num = jax.lax.psum(o_l.astype(jnp.float32) * w[..., None], seq_axes)
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(out_dtype)


def decode_attention_sharded(q, k_new, v_new, ck, cv, idx, *, mesh,
                             batch_axes: Tuple[str, ...],
                             seq_axes: Tuple[str, ...]):
    """q: (B,1,Hq,D); k_new/v_new: (B,1,Hkv,D); ck/cv: (B,S,Hkv,D);
    idx: scalar int32 (write position == number of valid tokens so far).
    Returns (out (B,1,Hq,D), new_ck, new_cv)."""
    B, S = ck.shape[0], ck.shape[1]
    Hq, D = q.shape[2], q.shape[3]
    Hkv = ck.shape[2]
    G = Hq // Hkv
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    chunk = S // n_seq
    scale = 1.0 / np.sqrt(D)

    b = batch_axes if batch_axes else None
    q_spec = PS(b, None, None, None)
    c_spec = PS(b, seq_axes, None, None)

    def local(q_l, kn, vn, ck_l, cv_l, idx_l):
        f32 = jnp.float32
        off = _axis_index(seq_axes, mesh) * chunk
        lpos = idx_l - off
        in_r = (lpos >= 0) & (lpos < chunk)
        li = jnp.clip(lpos, 0, chunk - 1)
        # write (or harmlessly rewrite) one row
        row_k = jax.lax.dynamic_slice_in_dim(ck_l, li, 1, 1)
        row_v = jax.lax.dynamic_slice_in_dim(cv_l, li, 1, 1)
        row_k = jnp.where(in_r, kn.astype(ck_l.dtype), row_k)
        row_v = jnp.where(in_r, vn.astype(cv_l.dtype), row_v)
        ck_n = jax.lax.dynamic_update_slice_in_dim(ck_l, row_k, li, 1)
        cv_n = jax.lax.dynamic_update_slice_in_dim(cv_l, row_v, li, 1)

        # local partial attention over my chunk
        qg = q_l.reshape(q_l.shape[0], Hkv, G, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, ck_n.astype(q_l.dtype),
                       preferred_element_type=f32) * scale
        pos = off + jnp.arange(chunk)
        valid = pos <= idx_l                       # includes the new token
        s = jnp.where(valid[None, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)                    # (b,h,g)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, cv_n.astype(q_l.dtype),
                       preferred_element_type=f32)
        # combine across seq shards (flash-decoding reduction)
        gm = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - gm)
        l = jax.lax.psum(l * corr, seq_axes)
        o = jax.lax.psum(o * corr[..., None], seq_axes)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(q_l.shape[0], 1, Hq, D), ck_n, cv_n

    fn = shard_map(local, mesh,
                   (q_spec, q_spec, q_spec, c_spec, c_spec, PS()),
                   (q_spec, c_spec, c_spec))
    return fn(q, k_new, v_new, ck, cv, idx)


def decode_paged_attention_sharded(q, k_new, v_new, ck, cv, pt, idx, *,
                                   mesh, batch_axes: Tuple[str, ...],
                                   seq_axes: Tuple[str, ...]):
    """Flash-decoding over a block-paged KV pool (serve/kv_cache.py).

    q: (B,1,Hq,D); k_new/v_new: (B,1,Hkv,D); ck/cv: (P,page,Hkv,D) page
    pool sharded in page chunks over `seq_axes`; pt: (B,M) page table
    (-1 = unmapped); idx: (B,) per-slot write positions (negative =
    idle, store dropped). Each shard scatters the one new row it owns
    through the page table, gathers its locally-owned pages into the
    logical per-slot view under a page-table-aware ownership mask, and
    the partials combine with the same pmax+psum flash reduction as the
    dense path. Returns (out (B,1,Hq,D), new_ck, new_cv)."""
    P, ps = ck.shape[0], ck.shape[1]
    Hq, D = q.shape[2], q.shape[3]
    Hkv = ck.shape[2]
    G = Hq // Hkv
    M = pt.shape[1]
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    chunk = P // n_seq                 # pages per shard
    scale = 1.0 / np.sqrt(D)
    use_pallas, interp = _pallas_mode()

    b = batch_axes if batch_axes else None
    q_spec = PS(b, None, None, None)
    pool_spec = PS(seq_axes, None, None, None)
    pt_spec = PS(b, None)
    idx_spec = PS(b)

    def local(q_l, kn, vn, ck_l, cv_l, pt_l, idx_l):
        f32 = jnp.float32
        off = _axis_index(seq_axes, mesh) * chunk
        # -- store: route the new row through the page table; only the
        # shard owning the target page writes (others — and idle slots
        # with negative positions or unmapped pages — drop)
        pi = jnp.floor_divide(idx_l, ps)
        page = jnp.where(
            (pi >= 0) & (pi < M),
            jnp.take_along_axis(pt_l, jnp.clip(pi, 0, M - 1)[:, None],
                                axis=1)[:, 0], -1)
        lp = page - off
        own_w = (page >= 0) & (lp >= 0) & (lp < chunk) & (idx_l >= 0)
        flat = jnp.where(own_w, lp * ps + jnp.remainder(idx_l, ps),
                         chunk * ps)

        def scat(pool, new):
            fp = pool.reshape((chunk * ps,) + pool.shape[2:])
            fp = fp.at[flat].set(new[:, 0].astype(pool.dtype), mode="drop")
            return fp.reshape(pool.shape)
        ck_n = scat(ck_l, kn)
        cv_n = scat(cv_l, vn)

        # -- gather: the slot's logical view from locally-owned pages
        lpt = pt_l - off                              # (B', M)
        owned = (pt_l >= 0) & (lpt >= 0) & (lpt < chunk)

        if use_pallas:
            # Pallas fast path: the decode kernel chases the LOCALIZED
            # page table (-1 on pages this shard does not own) so the
            # logical-view gather never materializes; partials combine
            # with the kernel's per-(slot, head) lse. Counters are
            # polluted by non-owner shards and ignored — the engine's
            # sharded path counts stores host-side (layers._finish).
            from repro.kernels.paged_attention import paged_decode_attention
            o_l, lse, _ = paged_decode_attention(
                q_l, kn, vn, ck_n, cv_n, jnp.where(owned, lpt, -1), idx_l,
                interpret=interp)
            out = _lse_combine(o_l, lse[:, None, :], seq_axes, q_l.dtype)
            return out, ck_n, cv_n

        kg = jnp.take(ck_n, jnp.clip(lpt, 0, chunk - 1), axis=0)
        vg = jnp.take(cv_n, jnp.clip(lpt, 0, chunk - 1), axis=0)
        Bl = pt_l.shape[0]
        kg = kg.reshape(Bl, M * ps, Hkv, D)
        vg = vg.reshape(Bl, M * ps, Hkv, D)
        pos = jnp.arange(M * ps)
        valid = (jnp.repeat(owned, ps, axis=1)
                 & (pos[None, :] <= idx_l[:, None]))  # incl. the new token

        # -- local partial attention + flash-decoding combine
        qg = q_l.reshape(Bl, Hkv, G, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kg.astype(q_l.dtype),
                       preferred_element_type=f32) * scale
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgk,bkhd->bhgd", p, vg.astype(q_l.dtype),
                       preferred_element_type=f32)
        gm = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - gm)
        l = jax.lax.psum(l * corr, seq_axes)
        o = jax.lax.psum(o * corr[..., None], seq_axes)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(Bl, 1, Hq, D), ck_n, cv_n

    fn = shard_map(local, mesh,
                   (q_spec, q_spec, q_spec, pool_spec, pool_spec,
                    pt_spec, idx_spec),
                   (q_spec, pool_spec, pool_spec))
    return fn(q, k_new, v_new, ck, cv, pt, idx)


def verify_paged_attention_sharded(q, k_new, v_new, ck, cv, pt, idx, *,
                                   mesh, batch_axes: Tuple[str, ...],
                                   seq_axes: Tuple[str, ...]):
    """Width-k speculative verify over a block-paged KV pool.

    The width-W generalization of `decode_paged_attention_sharded`
    (LM.verify's sharded fast path): q: (B,W,Hq,D) queries at logical
    positions idx[b]..idx[b]+W-1; k_new/v_new: (B,W,Hkv,D) the window's
    K/V; ck/cv: (P,page,Hkv,D) pool sharded in page chunks over
    `seq_axes`; pt: (B,M) page table; idx: (B,) per-slot window starts
    (negative = idle, stores drop). Each shard scatters the window rows
    whose pages it owns, gathers its owned pages into the logical view,
    masks per QUERY (position idx+i attends pos <= idx+i — the in-window
    causal chain), and partials combine with the same pmax+psum flash
    reduction. Returns (out (B,W,Hq,D), new_ck, new_cv)."""
    P, ps = ck.shape[0], ck.shape[1]
    B, W = q.shape[0], q.shape[1]
    Hq, D = q.shape[2], q.shape[3]
    Hkv = ck.shape[2]
    G = Hq // Hkv
    M = pt.shape[1]
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    chunk = P // n_seq                 # pages per shard
    scale = 1.0 / np.sqrt(D)
    use_pallas, interp = _pallas_mode()

    b = batch_axes if batch_axes else None
    q_spec = PS(b, None, None, None)
    pool_spec = PS(seq_axes, None, None, None)
    pt_spec = PS(b, None)
    idx_spec = PS(b)

    def local(q_l, kn, vn, ck_l, cv_l, pt_l, idx_l):
        f32 = jnp.float32
        off = _axis_index(seq_axes, mesh) * chunk
        Bl = pt_l.shape[0]

        if use_pallas:
            # Pallas fast path: the fused window kernel on the LOCALIZED
            # page table does the whole shard body — its store writes
            # exactly the window rows whose pages this shard owns
            # (store-mode window validity = "target page mapped", which
            # under the localized table means locally owned, so every
            # window row is attended and stored by exactly one shard),
            # its committed-history sweep covers the owned pages, and
            # the per-(slot, head, query) lse drives the cross-shard
            # combine. Counters are ignored here — the engine's sharded
            # path counts stores host-side (layers._finish).
            from repro.kernels.flash_prefill import paged_window_attention
            lpt = pt_l - off
            owned = (pt_l >= 0) & (lpt >= 0) & (lpt < chunk)
            o_l, lse, _, ck_n, cv_n = paged_window_attention(
                q_l, kn, vn, ck_l, cv_l, jnp.where(owned, lpt, -1), idx_l,
                store=True, interpret=interp)
            # lse: (B', Hq, W) -> (B', W, Hq) to match o_l
            out = _lse_combine(o_l, lse.transpose(0, 2, 1), seq_axes,
                               q_l.dtype)
            return out, ck_n, cv_n

        # -- store: route every window row through the page table; only
        # the shard owning the target page writes, everything else drops
        pos = idx_l[:, None] + jnp.arange(W)[None, :]        # (B', W)
        pi = jnp.floor_divide(pos, ps)
        page = jnp.where(
            (pi >= 0) & (pi < M),
            jnp.take_along_axis(pt_l, jnp.clip(pi, 0, M - 1), axis=1), -1)
        lp = page - off
        own_w = (page >= 0) & (lp >= 0) & (lp < chunk) & (pos >= 0)
        flat = jnp.where(own_w, lp * ps + jnp.remainder(pos, ps),
                         chunk * ps)

        def scat(pool, new):
            fp = pool.reshape((chunk * ps,) + pool.shape[2:])
            fp = fp.at[flat.reshape(-1)].set(
                new.reshape((-1,) + new.shape[2:]).astype(pool.dtype),
                mode="drop")
            return fp.reshape(pool.shape)
        ck_n = scat(ck_l, kn)
        cv_n = scat(cv_l, vn)

        # -- gather: the slot's logical view from locally-owned pages
        lpt = pt_l - off                                     # (B', M)
        owned = (pt_l >= 0) & (lpt >= 0) & (lpt < chunk)
        kg = jnp.take(ck_n, jnp.clip(lpt, 0, chunk - 1), axis=0)
        vg = jnp.take(cv_n, jnp.clip(lpt, 0, chunk - 1), axis=0)
        kg = kg.reshape(Bl, M * ps, Hkv, D)
        vg = vg.reshape(Bl, M * ps, Hkv, D)
        kpos = jnp.arange(M * ps)
        # per-query validity: query i at logical pos idx+i sees owned
        # positions <= idx+i (committed history + window rows <= i)
        valid = (jnp.repeat(owned, ps, axis=1)[:, None, :]
                 & (kpos[None, None, :] <= pos[:, :, None]))  # (B',W,Skv)

        # -- local partial attention + flash-decoding combine
        qg = q_l.reshape(Bl, W, Hkv, G, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg.astype(q_l.dtype),
                       preferred_element_type=f32) * scale
        s = jnp.where(valid[:, None, None, :, :], s, -1e30)
        m = jnp.max(s, axis=-1)                              # (b,h,g,q)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vg.astype(q_l.dtype),
                       preferred_element_type=f32)
        gm = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - gm)
        l = jax.lax.psum(l * corr, seq_axes)
        o = jax.lax.psum(o * jnp.moveaxis(corr, 3, 1)[..., None],
                         seq_axes)
        lq = jnp.moveaxis(l, 3, 1)                           # (b,q,h,g)
        out = (o / jnp.maximum(lq, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(Bl, W, Hq, D), ck_n, cv_n

    fn = shard_map(local, mesh,
                   (q_spec, q_spec, q_spec, pool_spec, pool_spec,
                    pt_spec, idx_spec),
                   (q_spec, pool_spec, pool_spec))
    return fn(q, k_new, v_new, ck, cv, pt, idx)


def cross_attention_sharded(q, ck, cv, *, mesh, batch_axes, seq_axes):
    """Read-only sharded cross-attention (precomputed KV, e.g. encoder out
    or image tokens). Same combine, no update."""
    B, S = ck.shape[0], ck.shape[1]
    Hq, D = q.shape[2], q.shape[3]
    Hkv = ck.shape[2]
    G = Hq // Hkv
    Sq = q.shape[1]
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    scale = 1.0 / np.sqrt(D)
    b = batch_axes if batch_axes else None
    q_spec = PS(b, None, None, None)
    c_spec = PS(b, seq_axes, None, None)

    def local(q_l, ck_l, cv_l):
        f32 = jnp.float32
        qg = q_l.reshape(q_l.shape[0], Sq, Hkv, G, D)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, ck_l.astype(q_l.dtype),
                       preferred_element_type=f32) * scale
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bqhgk,bkhd->bqhgd", p, cv_l.astype(q_l.dtype),
                       preferred_element_type=f32)
        gm = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - gm)
        l = jax.lax.psum(l * corr, seq_axes)
        o = jax.lax.psum(o * corr[..., None], seq_axes)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        return out.reshape(q_l.shape[0], Sq, Hq, D)

    fn = shard_map(local, mesh, (q_spec, c_spec, c_spec), q_spec)
    return fn(q, ck, cv)


def paged_shard_plan(sharder, batch: int, num_pages: int, page_size: int):
    """Shard plan for a paged pool: pages chunk over 'model' (the dense
    plan's sequence role); batch over dp when divisible. None = run the
    single-device gather/scatter fallback."""
    if sharder is None or "model" not in sharder.mesh.shape:
        return None
    mesh = sharder.mesh
    if num_pages * page_size < 1024 or num_pages % mesh.shape["model"]:
        return None
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))
    return (dp if batch % dpn == 0 else ()), ("model",)


def decode_shard_plan(sharder, batch: int, seq: int):
    """Mirror of TpServe.cache_specs: (batch_axes, seq_axes) or None."""
    if sharder is None or "model" not in sharder.mesh.shape:
        return None
    mesh = sharder.mesh
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))
    if batch % dpn == 0:
        if seq >= 1024 and seq % mesh.shape["model"] == 0:
            return dp, ("model",)
        return None
    full = dp + ("model",)
    n = int(np.prod([mesh.shape[a] for a in full]))
    if seq >= 1024 and seq % n == 0:
        return (), full
    if seq >= 1024 and seq % mesh.shape["model"] == 0:
        return (), ("model",)
    return None

"""jit'd wrappers + backend dispatch for the Pallas kernels: the one
module that picks which attention implementation runs.

Two observable facts pick the path. The active sharder first: when its
mesh admits a shard plan for the cache (``sharded.paged_shard_plan`` /
``decode_shard_plan``), the flash-decoding entries of ``sharded.py``
run. Then the platform: on a TPU the Pallas kernels run compiled;
elsewhere the pure-jnp oracles from ``ref.py`` run — they are the same
math and XLA/GSPMD handles fusion + partitioning. Tests steer the
dispatch themselves (monkeypatching ``_use_pallas``, which the sharded
bodies read too) to run the kernels in interpret mode on CPU against
the oracles.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import sharded as _sh
from repro.sharding.ctx import current_sharder, shard, shard_map


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _pallas_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _per_batch_shard(kernel, *xs):
    """Run a Pallas kernel on each device's shard of the batch dim.

    GSPMD cannot partition a Mosaic call, so under a multi-device mesh
    (the active sharding context) the kernel runs inside a shard_map over
    the context's batch axes — or on the whole batch on every device when
    the batch does not divide them."""
    sharder = current_sharder()
    if sharder is None or sharder.mesh.size == 1:
        return kernel(*xs)
    axes = sharder.batch_axes
    n = int(np.prod([sharder.mesh.shape[a] for a in axes]))
    spec = PS(axes) if xs[0].shape[0] % n == 0 else PS()
    return shard_map(kernel, sharder.mesh, (spec,) * len(xs), spec)(*xs)


# ----------------------------------------------------------------------
# KV length above which the O(S^2)-memory reference path is replaced by the
# flash (chunked online-softmax, custom-vjp) path.
FLASH_THRESHOLD = 1024


def attention(q, k, v, *, causal: bool = True, q_offset=0,
              kv_len: Optional[jax.Array] = None,
              kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Model-facing attention entry point (GQA)."""
    sq, skv = q.shape[1], k.shape[1]
    if (kv_len is None and kv_valid is None
            and isinstance(q_offset, int) and q_offset == 0):
        if _use_pallas() and sq >= 8:
            return _per_batch_shard(partial(
                _fa.flash_attention, causal=causal,
                interpret=_pallas_interpret()), q, k, v)
        if skv >= FLASH_THRESHOLD:
            from repro.kernels.flash_xla import flash_xla
            return flash_xla(q, k, v, causal, 0)
    return _ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                              kv_len=kv_len, kv_valid=kv_valid)


def cached_decode(q, k_new, v_new, cache_k, cache_v, idx):
    """Decode against a dense (B, Smax, Hkv, D) cache at one scalar
    write position ``idx`` for the whole batch: write the S new K/V rows
    at ``idx`` and attend over [0, idx + S). Returns ``(out, ck, cv)``.

    One token against a sequence-chunk-sharded cache runs the sharded
    flash-decoding entry; anything else updates the cache in place and
    runs ``attention`` over it."""
    S = q.shape[1]
    sharder = current_sharder()
    plan = (_sh.decode_shard_plan(sharder, k_new.shape[0],
                                  cache_k.shape[1]) if S == 1 else None)
    if plan is not None:
        b_ax, s_ax = plan
        return _sh.decode_attention_sharded(
            q, k_new, v_new, cache_k, cache_v, idx, mesh=sharder.mesh,
            batch_axes=b_ax, seq_axes=s_ax)
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), idx, axis=1)
    k, v = ck.astype(q.dtype), cv.astype(q.dtype)
    kv_len = idx + S
    out = attention(shard(q, "bshd"), shard(k, "bskv"), shard(v, "bskv"),
                    causal=True, q_offset=idx, kv_len=kv_len)
    return out, ck, cv


def cross_attention(q, k, v, *, kv_valid: Optional[jax.Array] = None):
    """Non-causal attention against precomputed K/V (image tokens,
    encoder output). A sequence-sharded K/V without a length mask runs
    the sharded combine; otherwise keys past ``kv_valid`` never
    contribute."""
    sharder = current_sharder()
    plan = (_sh.decode_shard_plan(sharder, q.shape[0], k.shape[1])
            if kv_valid is None else None)
    if plan is not None:
        b_ax, s_ax = plan
        return _sh.cross_attention_sharded(q, k, v, mesh=sharder.mesh,
                                           batch_axes=b_ax, seq_axes=s_ax)
    return attention(q, k.astype(q.dtype), v.astype(q.dtype), causal=False,
                     kv_valid=kv_valid)


# paged-KV scatter/gather: pure-jnp (XLA scatter/gather fuse well and
# GSPMD partitions them); re-exported here so model code dispatches
# through one kernel namespace
paged_update = _ref.paged_update
paged_gather = _ref.paged_gather
paged_store_counts = _ref.paged_store_counts

# store-site waste-counter tolerance (kernel tier): exact equality, the
# paper's Def.-2 silent-store semantics for same-dtype overwrites
COUNTER_TOL = 0.0


def _sharded_paged(entry, q, k_new, v_new, pool_k, pool_v, pt, idx, *,
                   layer, counters):
    """Run a sharded paged entry when the active sharder has a plan for
    the pool, else return None. Each shard scatters the rows it owns and
    the per-query partials combine flash-style; the shard_map takes one
    layer's pool, so a stacked pool's layer is cut out and written back.
    The shard bodies' kernel counters are ignored, so the stores are
    counted here, outside the shard_map."""
    sharder = current_sharder()
    plan = _sh.paged_shard_plan(sharder, q.shape[0], pool_k.shape[-4],
                                pool_k.shape[-3])
    if plan is None:
        return None
    b_ax, s_ax = plan
    lk, lv = ((pool_k, pool_v) if layer is None
              else (pool_k[layer], pool_v[layer]))
    out, ck, cv = entry(q, k_new, v_new, lk, lv, pt, idx, mesh=sharder.mesh,
                        batch_axes=b_ax, seq_axes=s_ax)
    if layer is not None:
        ck, cv = pool_k.at[layer].set(ck), pool_v.at[layer].set(cv)
    cnt = (paged_store_counts(pool_k, pool_v, k_new, v_new, pt, idx,
                              tol=COUNTER_TOL, layer=layer)
           if counters else None)
    return out, ck, cv, cnt


def paged_decode(q, k_new, v_new, pool_k, pool_v, pt, idx, *,
                 layer=None, counters: bool = False):
    """One-token paged-attention decode: attend slot history + the new
    K/V row, scatter the row through the page table.

    ``pool_k``/``pool_v`` are one layer's (P, page, Hkv, D) pools, or
    the layer-stacked (L, P, page, Hkv, D) pools with ``layer`` the
    layer attended: the decode scan carries the stacked pool, and the
    row lands in place at [layer, page, offset] of it.

    Returns ``(out, ck, cv, cnt)`` — cnt is the (B, 3) int32 store-site
    waste counter block ([stored, silent, dropped] elements, see
    ``kernels/paged_attention.py``) or None when ``counters=False``.

    Pallas path: the kernel gathers K/V pages in-kernel via the
    scalar-prefetched page table (no logical-view materialization) and
    measures the counters at the store site; only the O(B*Hkv*D)
    single-row scatter runs outside. Ref path: the scatter-gather-mask
    composition from ``ref.py``. A page-chunk-sharded pool runs
    ``sharded.decode_paged_attention_sharded``.
    """
    done = _sharded_paged(_sh.decode_paged_attention_sharded, q, k_new,
                          v_new, pool_k, pool_v, pt, idx, layer=layer,
                          counters=counters)
    if done is not None:
        return done
    if _use_pallas():
        from repro.kernels.paged_attention import paged_decode_attention
        out, _, cnt = paged_decode_attention(
            q, k_new, v_new, pool_k, pool_v, pt, idx, layer=layer,
            tol=COUNTER_TOL, interpret=_pallas_interpret())
        ck, cv = _ref.paged_update(pool_k, pool_v, k_new, v_new, pt, idx,
                                   layer=layer)
        return out, ck, cv, (cnt if counters else None)
    out, ck, cv, cnt = _ref.paged_decode_ref(
        q, k_new, v_new, pool_k, pool_v, pt, idx, tol=COUNTER_TOL,
        layer=layer)
    return out, ck, cv, (cnt if counters else None)


def paged_window(q, k_win, v_win, pool_k, pool_v, pt, idx, *,
                 layer=None, store: bool = True, verify: bool = False,
                 counters: bool = False):
    """S-token paged window forward (prefill chunk / width-k verify):
    attend committed history + the in-window causal part, and — store
    mode — write the window rows into the pool through the page table.
    Pools and ``layer`` as in ``paged_decode``.

    Returns ``(out, ck, cv, cnt)`` like ``paged_decode``; with
    ``store=False`` ("defer"/rollback verify) the pool is untouched and
    cnt is all-zero (no machine-level stores happen). ``verify`` marks a
    stored width-k verify window, which against a page-chunk-sharded
    pool runs ``sharded.verify_paged_attention_sharded``.
    """
    if store and verify:
        done = _sharded_paged(_sh.verify_paged_attention_sharded, q, k_win,
                              v_win, pool_k, pool_v, pt, idx, layer=layer,
                              counters=counters)
        if done is not None:
            return done
    if _use_pallas():
        from repro.kernels.flash_prefill import paged_window_attention
        out, _, cnt, ck, cv = paged_window_attention(
            q, k_win, v_win, pool_k, pool_v, pt, idx, layer=layer,
            store=store, tol=COUNTER_TOL, interpret=_pallas_interpret())
        return out, ck, cv, (cnt if counters else None)
    out, ck, cv, cnt = _ref.paged_window_ref(
        q, k_win, v_win, pool_k, pool_v, pt, idx, store=store,
        tol=COUNTER_TOL, layer=layer)
    return out, ck, cv, (cnt if counters else None)


@partial(jax.jit, static_argnames=("tol",))
def silent_fraction(a, b, tol: float = 0.01):
    """Fraction of silent (unchanged within tol) elements between a and b."""
    n = a.size
    cnt = _ref.silent_compare_ref(a, b, tol)
    return cnt.astype(jnp.float32) / max(n, 1)

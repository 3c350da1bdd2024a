"""jit'd wrappers + backend dispatch for the Pallas kernels.

The platform alone picks the path: on a TPU the Pallas kernels run
compiled; elsewhere the pure-jnp oracles from ``ref.py`` run — they are
the same math and XLA/GSPMD handles fusion + partitioning. Tests steer
the dispatch themselves (monkeypatching ``_use_pallas``) to run the
kernels in interpret mode on CPU against the oracles.
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
from repro.sharding.ctx import current_sharder
from repro.train.fused_xent import shard_map


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


# paged-KV scatter/gather: pure-jnp (XLA scatter/gather fuse well and
# GSPMD partitions them); re-exported here so model code dispatches
# through one kernel namespace
paged_update = _ref.paged_update
paged_gather = _ref.paged_gather
paged_store_counts = _ref.paged_store_counts

# store-site waste-counter tolerance (kernel tier): exact equality, the
# paper's Def.-2 silent-store semantics for same-dtype overwrites
COUNTER_TOL = 0.0


def paged_decode(q, k_new, v_new, pool_k, pool_v, pt, idx, *,
                 counters: bool = False):
    """One-token paged-attention decode: attend slot history + the new
    K/V row, scatter the row through the page table.

    Returns ``(out, ck, cv, cnt)`` — cnt is the (B, 3) int32 store-site
    waste counter block ([stored, silent, dropped] elements, see
    ``kernels/paged_attention.py``) or None when ``counters=False``.

    Pallas path: the kernel gathers K/V pages in-kernel via the
    scalar-prefetched page table (no logical-view materialization) and
    measures the counters at the store site; only the O(B*Hkv*D)
    single-row scatter runs outside. Ref path: the scatter-gather-mask
    composition from ``ref.py``.
    """
    if _use_pallas():
        from repro.kernels.paged_attention import paged_decode_attention
        out, _, cnt = paged_decode_attention(
            q, k_new, v_new, pool_k, pool_v, pt, idx,
            tol=COUNTER_TOL, interpret=_pallas_interpret())
        ck, cv = _ref.paged_update(pool_k, pool_v, k_new, v_new, pt, idx)
        return out, ck, cv, (cnt if counters else None)
    cnt = None
    if counters:
        cnt = _ref.paged_store_counts(pool_k, pool_v, k_new, v_new, pt, idx,
                                      tol=COUNTER_TOL)
    dt = q.dtype
    ck, cv = _ref.paged_update(pool_k, pool_v, k_new, v_new, pt, idx)
    gk, valid = _ref.paged_gather(ck, pt)
    gv, _ = _ref.paged_gather(cv, pt)
    out = _ref.attention_ref(q, gk.astype(dt), gv.astype(dt), causal=True,
                             q_offset=idx, kv_len=idx + 1, kv_valid=valid)
    return out, ck, cv, cnt


def paged_window(q, k_win, v_win, pool_k, pool_v, pt, idx, *,
                 store: bool = True, counters: bool = False):
    """S-token paged window forward (prefill chunk / width-k verify):
    attend committed history + the in-window causal part, and — store
    mode — write the window rows into the pool through the page table.

    Returns ``(out, ck, cv, cnt)`` like ``paged_decode``; with
    ``store=False`` ("defer"/rollback verify) the pool is untouched and
    cnt is all-zero (no machine-level stores happen).
    """
    if _use_pallas():
        from repro.kernels.flash_prefill import paged_window_attention
        out, _, cnt, ck, cv = paged_window_attention(
            q, k_win, v_win, pool_k, pool_v, pt, idx, store=store,
            tol=COUNTER_TOL, interpret=_pallas_interpret())
        return out, ck, cv, (cnt if counters else None)
    out, ck, cv, cnt = _ref.paged_window_ref(
        q, k_win, v_win, pool_k, pool_v, pt, idx, store=store,
        tol=COUNTER_TOL)
    return out, ck, cv, (cnt if counters else None)


@partial(jax.jit, static_argnames=("tol",))
def silent_fraction(a, b, tol: float = 0.01):
    """Fraction of silent (unchanged within tol) elements between a and b."""
    n = a.size
    cnt = _ref.silent_compare_ref(a, b, tol)
    return cnt.astype(jnp.float32) / max(n, 1)

"""Fused width-(k+1) speculative-verify kernel (Pallas TPU).

A thin mode wrapper over the paged window kernel
(``kernels/flash_prefill.py``): verify pushes the k+1-token draft
window against the paged pool exactly like prefill pushes a prompt
chunk — same in-kernel page-table gather, same store-site counters — the
only degree of freedom is what happens to the pool:

  * ``mode="overwrite"`` (``LM.verify(commit=True)``): all k+1 window
    rows are stored through the page table. Rows past the accepted
    prefix are *rejected draft stores* — the kernel's store-site
    counters measure every stored element, and the engine's kernel-tier
    classification (which knows the acceptance length) attributes the
    rejected fraction: 1 − accept-rate, measured from inside the kernel.
  * ``mode="defer"`` (rollback): the pool is untouched; the kernel only
    computes the spliced-window attention and the counters stay zero.
    The accepted prefix is committed afterwards by ``LM.commit_verify``
    (a counted ``paged_update``), so the kernel-tier
    ``rejected_draft_store`` fraction is exactly 0 — rejected rows
    never become machine-level stores at all.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_prefill import paged_window_attention


def paged_verify_attention(q: jax.Array, k_win: jax.Array, v_win: jax.Array,
                           pool_k: jax.Array, pool_v: jax.Array,
                           pt: jax.Array, idx: jax.Array, *,
                           mode: str = "overwrite",
                           block_q: int = 128,
                           tol: float = 0.0,
                           interpret: bool = False):
    """q/k_win/v_win: (B, k+1, H*, D) at per-slot offsets ``idx``.

    Returns ``(out, lse, counters, new_pool_k, new_pool_v)`` — see
    ``paged_window_attention``; the pools come back unchanged in
    ``defer`` mode."""
    assert mode in ("overwrite", "defer"), mode
    return paged_window_attention(
        q, k_win, v_win, pool_k, pool_v, pt, idx,
        store=(mode == "overwrite"), block_q=block_q, tol=tol,
        interpret=interpret)

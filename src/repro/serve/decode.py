"""Serve-step factories: one-token batched decode, batched prefill and
the continuous-batching engine's tick/prefill/verify, all sharded-cache
aware.

Every factory wraps the model call in ``sharding_ctx``. With
``tp_serve`` the cache is sequence-chunk (or page-chunk) sharded over
"model", and ``kernels/ops.py`` then runs the flash-decoding entries of
``kernels/sharded.py``: each shard computes attention over its chunk and
the partials combine with one pmax+psum. Works for any head count and
any cache length (incl. 500k). So ``serve.engine.ServeEngine`` composes
with distribution strategies instead of duplicating an unsharded decode
step: the engine jits these factories directly (dense and paged KV
layouts alike).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sharding.ctx import sharding_ctx


class StepCache:
    """Shared jitted engine steps for a replica fleet.

    `jax.jit` caches per function OBJECT, so N `ServeEngine`s built the
    plain way compile the tick/prefill/verify factories N times over —
    pure compile-time waste for replicas serving the same model (they
    already share one weight arena). A fleet builds one StepCache and
    passes it to every `ServeEngine(step_cache=...)`; each distinct
    (kind, paged, rollback) combination compiles once and every replica
    dispatches through the same executable. Also what makes routing-
    policy A/B timing honest: both fleets run literally the same
    compiled code. A `ServeEngine` handed none builds its own.

    Every program donates its cache argument: after a call the cache
    passed in is deleted, and only the returned one may be used."""

    def __init__(self, model, strategy=None):
        self.model = model
        self.strategy = strategy
        self._fns = {}

    def get(self, kind: str, *, paged: bool = False,
            rollback: bool = False):
        key = (kind, bool(paged), bool(rollback))
        fn = self._fns.get(key)
        if fn is None:
            if kind == "tick":
                step = make_engine_tick(self.model, self.strategy,
                                        paged=paged)
            elif kind == "prefill":
                step = make_engine_prefill(self.model, self.strategy,
                                           paged=paged)
            elif kind == "verify":
                step = make_engine_verify(self.model, self.strategy,
                                          paged=paged, rollback=rollback)
            elif kind == "page_copy":
                from repro.serve.kv_cache import make_page_copy
                step = make_page_copy()
            else:
                raise ValueError(f"unknown step kind {kind!r}")
            # every program donates the cache it is handed (never the
            # params): the KV pool is updated in place and handed back
            # aliased, so one pool is live, not two
            fn = jax.jit(step, donate_argnums=(0,) if kind == "page_copy"
                         else (1,))
            self._fns[key] = fn
        return fn


def make_serve_step(model, strategy=None, greedy: bool = True):
    sharder = strategy.sharder() if strategy is not None else None

    def serve_step(params, cache, tokens):
        """tokens: (B,1) int32 -> (next_tokens (B,1), new_cache)."""
        with sharding_ctx(sharder):
            logits, new_cache = model.decode_step(params, cache, tokens)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, new_cache
    return serve_step


def make_prefill_step(model, strategy=None):
    def prefill_step(params, batch):
        with sharding_ctx(strategy.sharder() if strategy else None):
            logits, _ = model.forward(
                params, batch["tokens"],
                img=batch.get("img"), frames=batch.get("frames"))
        return logits
    return prefill_step


# ----------------------------------------------------------------------
# Continuous-batching engine steps (serve/engine.py jits these)
# ----------------------------------------------------------------------
def make_engine_tick(model, strategy=None, *, paged: bool = False):
    """One decode tick over the whole slot batch.

    Dense layout: idle slots freeze token AND write index, so every tick
    rewrites the same K/V site with the same value — the serving-tier
    dead/silent store the detectors trap on. Paged layout: idle slots'
    write positions drop to a sentinel below the page-table extent, so
    the scatter DROPS their store — the detected waste, eliminated."""
    sharder = strategy.sharder() if strategy is not None else None

    def tick(params, cache, tokens, active):
        idx0 = model.cache_index(cache)            # (B,)
        stepped = cache
        if paged:
            stepped = model.with_cache_index(
                cache, jnp.where(active, idx0, -2))
        with sharding_ctx(sharder):
            logits, new_cache = model.decode_step(params, stepped, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        nxt = jnp.where(active[:, None], nxt[:, None], tokens)
        new_cache = model.with_cache_index(
            new_cache, jnp.where(active, idx0 + 1, idx0))
        return nxt, new_cache
    return tick


def make_engine_verify(model, strategy=None, *, paged: bool = False,
                       rollback: bool = False):
    """One speculative verify tick over the whole slot batch.

    tokens: (B, W) = [last accepted token, draft_1 .. draft_{W-1}] per
    slot (width fixed so one compiled shape serves every tick; unused
    draft positions are padding); active: (B,) bool; draft_len: (B,)
    number of REAL drafts in each row (0 = plain decode for that slot).

    Greedy acceptance on-device: draft j+1 is accepted iff it equals the
    verify forward's own greedy token at position j and every earlier
    draft was accepted, so the emitted chain g[:, 0..m] is exactly what
    plain one-token decode would have produced — bit-identical outputs,
    up to W tokens per tick. Returns (g (B,W) greedy tokens, m (B,)
    accepted-draft counts, next_tokens (B,1) = the bonus token g[:, m],
    new_cache with per-slot indices advanced by 1+m).

    rollback=True (paged only): the verify forward defers its K/V
    stores and the accepted prefix is committed in the same jitted call
    (`LM.commit_verify`) — rejected draft rows never reach the pool.
    rollback=False: all W rows are stored and the index rolls back over
    the rejected tail, which the next window overwrites (the Def.-1
    dead stores `rejected_draft_store` counts)."""
    sharder = strategy.sharder() if strategy is not None else None

    def verify(params, cache, tokens, active, draft_len):
        B, W = tokens.shape
        idx0 = model.cache_index(cache)            # (B,)
        stepped = cache
        if paged:
            # idle slots: every window position maps below the page
            # table, so their stores drop (same sentinel idea as tick)
            stepped = model.with_cache_index(
                cache, jnp.where(active, idx0, -(W + 1)))
        with sharding_ctx(sharder):
            logits, new_cache = model.verify(params, stepped, tokens,
                                             commit=not rollback)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B, W)
        ok = ((tokens[:, 1:] == g[:, :-1])
              & (jnp.arange(W - 1)[None, :] < draft_len[:, None]))
        m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        m = jnp.where(active, m, 0)
        if rollback:
            new_cache = model.commit_verify(
                new_cache, idx0, jnp.where(active, 1 + m, 0))
        nxt = jnp.take_along_axis(g, m[:, None], axis=1)
        nxt = jnp.where(active[:, None], nxt, tokens[:, :1])
        new_cache = model.with_cache_index(
            new_cache, jnp.where(active, idx0 + 1 + m, idx0))
        return g, m, nxt, new_cache
    return verify


def make_engine_prefill(model, strategy=None, *, paged: bool = False):
    """Grouped admission prefill.

    toks: (B,P) right-padded prompts — full prompts in dense mode, the
    uncached suffixes (prompt minus the reused prefix) in paged mode;
    admit: (B,) bool; start: (B,) cached-prefix lengths (all zero in
    dense mode); lengths: (B,) full prompt lengths; prev_tokens: (B,1)
    tokens of non-admitted rows, passed through untouched.

    Dense: the whole refilled cache is tree-merged back under the admit
    mask. Paged: stores already scatter through each slot's page table
    (non-admitted rows get a sentinel index and write nothing), so no
    merge pass exists — only the write indices are restored."""
    sharder = strategy.sharder() if strategy is not None else None

    def prefill(params, cache, toks, admit, start, lengths, prev_tokens):
        B, P = toks.shape
        idx0 = model.cache_index(cache)
        if paged:
            fresh = model.with_cache_index(
                cache, jnp.where(admit, start, -(P + 1)))
        else:
            fresh = model.with_cache_index(cache, jnp.zeros((B,), jnp.int32))
        with sharding_ctx(sharder):
            logits, filled = model.prefill(params, fresh, toks)
        if not paged:
            def sel(n, o):
                m = admit.reshape((1, -1) + (1,) * (n.ndim - 2))
                return jnp.where(m, n, o)
            filled = jax.tree_util.tree_map(sel, filled, cache)
        merged = model.with_cache_index(
            filled, jnp.where(admit, lengths, idx0))
        sel_pos = jnp.clip(lengths - start - 1, 0, P - 1)
        first = jnp.argmax(
            logits[jnp.arange(B), sel_pos], axis=-1).astype(jnp.int32)
        toks_out = jnp.where(admit[:, None], first[:, None], prev_tokens)
        return toks_out, merged
    return prefill

"""Core transformer layers: norms, RoPE, GQA attention (qk-norm, cross-attn,
KV-cache decode), gated/plain MLP. Functional style: ``decl_*`` builds the
parameter declaration tree, ``apply_*`` consumes the materialized params.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.sharding.ctx import shard


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def apply_rmsnorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, D/2)
    if ang.ndim == 2:                                  # (S, D/2) -> (1, S, D/2)
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]                  # (B|1, S, 1, D/2)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Attention (GQA; ref path — the Pallas flash kernel is dispatched in
# repro.kernels.ops for TPU deployments)
# ----------------------------------------------------------------------
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool, q_offset=0,
              kv_len: Optional[jax.Array] = None,
              kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """q: (B,Sq,Hq,D), k/v: (B,Skv,Hkv,D) -> (B,Sq,Hq,D).

    GQA via head grouping; scores accumulated in f32. ``q_offset`` is the
    absolute position of q[0] (for decode); ``kv_len`` masks cache slots
    >= kv_len (decode with preallocated cache); ``kv_valid`` masks
    unmapped page-table positions of a paged cache's gathered view.
    """
    from repro.kernels import ops
    return ops.attention(q, k, v, causal=causal, q_offset=q_offset,
                         kv_len=kv_len, kv_valid=kv_valid)


def decl_attention(cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    decl = {
        "wq": P.linear(d, cfg.q_dim, "embed", "q_feat"),
        "wk": P.linear(d, cfg.kv_dim, "embed", "kv_feat"),
        "wv": P.linear(d, cfg.kv_dim, "embed", "kv_feat"),
        "wo": P.linear(cfg.q_dim, d, "q_feat", "embed"),
    }
    if cfg.qk_norm:
        decl["q_norm"] = P.norm(cfg.head_dim, None)
        decl["k_norm"] = P.norm(cfg.head_dim, None)
    return decl


def apply_attention(p, cfg: ModelConfig, x: jax.Array, *,
                    kv_src: Optional[jax.Array] = None,
                    positions: Optional[jax.Array] = None,
                    causal: bool = True,
                    cache: Optional[Dict[str, jax.Array]] = None,
                    use_rope: bool = True,
                    spec: Optional[str] = None,
                    kv_valid: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention with optional KV cache.

    cache: {"k": (B,Smax,Hkv,D), "v": ..., "idx": scalar int32} — decode
    writes the new K/V at idx and attends over [0, idx+len).

    ``kv_valid``: optional (B, Skv) key-validity mask for the cache-free
    paths (encoder self-attention over right-padded frames, cross-attn
    over a padded source): masked keys never contribute, so outputs on
    valid rows are independent of the padded extent — what makes
    length-bucketed encoder prefill bit-identical to padded-to-capacity.

    ``spec`` marks a speculative width-k verify forward (LM.verify):
      "overwrite" — all S window rows are stored, but bounded: rows past
          the cache extent / page table drop instead of clamp-shifting
          onto committed history (rejected rows become Def.-1 dead
          stores, the waste `rejected_draft_store` measures);
      "defer" (paged only) — the pool is untouched; the window's K/V
          ride in ``win_k``/``win_v`` for `LM.commit_verify` to scatter
          only the accepted prefix (rollback: rejected rows never become
          cache stores at all).
    """
    B, S, _ = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype

    q = (x @ p["wq"]["w"].astype(dt)).reshape(B, S, H, D)
    src = x if kv_src is None else kv_src
    Bk, Skv = src.shape[:2]
    k = (src @ p["wk"]["w"].astype(dt)).reshape(Bk, Skv, Hkv, D)
    v = (src @ p["wv"]["w"].astype(dt)).reshape(Bk, Skv, Hkv, D)

    if cfg.qk_norm:
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)

    q_offset = 0
    if use_rope and kv_src is None:
        if cache is not None:
            idx0 = cache["idx"]
            if jnp.ndim(idx0) == 1:              # per-slot positions (B,)
                pos_q = idx0[:, None] + jnp.arange(S)[None, :]
            else:
                pos_q = (idx0 + jnp.arange(S))[None, :]
            q = apply_rope(q, pos_q, cfg.rope_theta)
            k = apply_rope(k, pos_q, cfg.rope_theta)
        else:
            pos = positions if positions is not None else jnp.arange(S)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)

    new_cache = None
    kv_len = None
    if (cache is not None and kv_src is None and "pt" in cache
            and spec == "defer"):
        # speculative verify, rollback mode: the pool is NOT written.
        # Attention runs over the committed history with the verify
        # window spliced in at its positions — pure activation memory —
        # and the window K/V ride in win_k/win_v for LM.commit_verify to
        # scatter only the accepted prefix. Values round-trip through
        # the pool dtype exactly like the scatter-then-gather path, so
        # the logits are bit-identical to overwrite mode. Dispatches to
        # the fused paged window kernel (store disabled) on Pallas
        # backends, the spliced-gather ref composition elsewhere.
        from repro.kernels import ops
        idx = cache["idx"]
        pt = cache["pt"]
        counters = "kcnt" in cache
        out, _, _, cnt = ops.paged_window(
            q, k, v, cache["k"], cache["v"], pt, idx,
            layer=cache.get("layer"), store=False, counters=counters)
        new_cache = {**cache, "idx": idx + S, "win_k": k, "win_v": v}
        if counters:
            new_cache["kcnt"] = cnt     # all-zero: no stores in defer mode
        out = out.reshape(B, S, H * D)
        out = out @ p["wo"]["w"].astype(dt)
        return shard(out, "btd"), new_cache
    elif cache is not None and kv_src is None and "pt" in cache:
        # block-paged cache (serve/kv_cache.py): pool (P,page,Hkv,D),
        # page table (B,M), per-slot positions (B,). Stores scatter
        # through the table (out-of-table/idle writes DROP — no dead
        # rewrites); reads gather the logical view back, masked where
        # the table is unmapped. With a "layer" entry the pools are the
        # decode scan's layer-stacked (L,P,page,Hkv,D) carry, read and
        # written in place at that layer.
        from repro.kernels import ops
        idx = cache["idx"]
        pt = cache["pt"]
        layer = cache.get("layer")
        pool_k, pool_v = cache["k"], cache["v"]
        counters = "kcnt" in cache

        def _finish(out, ck, cv, cnt):
            new_cache = {**cache, "k": ck, "v": cv, "idx": idx + S}
            if counters:
                if cnt is None:       # sharded paths count host-side
                    cnt = ops.paged_store_counts(
                        pool_k, pool_v, k, v, pt, idx,
                        tol=ops.COUNTER_TOL, layer=layer)
                new_cache["kcnt"] = cnt
            out = out.reshape(B, S, H * D)
            out = out @ p["wo"]["w"].astype(dt)
            return shard(out, "btd"), new_cache

        def _sharded(entry):
            # a page-chunk-sharded pool: each shard scatters the rows
            # it owns and the per-query partials combine flash-style;
            # the shard_map takes one layer's pool, so a stacked pool's
            # layer is cut out and written back
            from repro.serve.flash_decode import paged_shard_plan
            from repro.sharding.ctx import current_sharder
            sharder = current_sharder()
            plan = paged_shard_plan(sharder, B, pool_k.shape[-4],
                                    pool_k.shape[-3])
            if plan is None:
                return None
            b_ax, s_ax = plan
            lk, lv = ((pool_k, pool_v) if layer is None
                      else (pool_k[layer], pool_v[layer]))
            out, ck, cv = entry(q, k, v, lk, lv, pt, idx, mesh=sharder.mesh,
                                batch_axes=b_ax, seq_axes=s_ax)
            if layer is not None:
                ck, cv = pool_k.at[layer].set(ck), pool_v.at[layer].set(cv)
            return _finish(out, ck, cv, None)

        if S == 1:
            from repro.serve.flash_decode import (
                decode_paged_attention_sharded)
            done = _sharded(decode_paged_attention_sharded)
            if done is not None:
                return done
            out, ck, cv, cnt = ops.paged_decode(
                q, k, v, pool_k, pool_v, pt, idx, layer=layer,
                counters=counters)
            return _finish(out, ck, cv, cnt)
        if spec == "overwrite":
            # width-k speculative verify against a sharded pool
            from repro.serve.flash_decode import (
                verify_paged_attention_sharded)
            done = _sharded(verify_paged_attention_sharded)
            if done is not None:
                return done
        # prefill chunk / verify-overwrite window: fused window forward
        # (Pallas kernel with in-kernel page gather + paged-write
        # epilogue, or the scatter-then-gather ref composition)
        out, ck, cv, cnt = ops.paged_window(
            q, k, v, pool_k, pool_v, pt, idx, layer=layer,
            store=True, counters=counters)
        return _finish(out, ck, cv, cnt)
    elif cache is not None and kv_src is None:
        idx = cache["idx"]
        if S == 1 and jnp.ndim(idx) == 0:
            # one-token decode: sharded flash-decoding when the cache is
            # sequence-chunk sharded (see serve/flash_decode.py)
            from repro.serve.flash_decode import (decode_attention_sharded,
                                                  decode_shard_plan)
            from repro.sharding.ctx import current_sharder
            sharder = current_sharder()
            plan = decode_shard_plan(sharder, Bk if kv_src is None else B,
                                     cache["k"].shape[1])
            if plan is not None:
                b_ax, s_ax = plan
                out, ck, cv = decode_attention_sharded(
                    q, k, v, cache["k"], cache["v"], idx,
                    mesh=sharder.mesh, batch_axes=b_ax, seq_axes=s_ax)
                new_cache = {"k": ck, "v": cv, "idx": idx + S}
                out = out.reshape(B, S, H * D)
                out = out @ p["wo"]["w"].astype(dt)
                return shard(out, "btd"), new_cache
        # fallback: in-place update + masked attention (single device /
        # unshardable shapes)
        if jnp.ndim(idx) == 1 and spec is not None:
            # width-k verify over dense per-slot rows: a bounded scatter
            # instead of the DUS below — DUS clamps an overflowing start
            # index, which would shift the window back onto committed
            # history; here rows past the cache extent simply drop
            # (committed tokens never reach there, only rejected drafts
            # and padding — see LM.verify)
            Smax = cache["k"].shape[1]
            pos = idx[:, None] + jnp.arange(S)[None, :]
            tgt = jnp.where((pos >= 0) & (pos < Smax), pos, Smax)
            bidx = jnp.arange(B)[:, None]
            ck = cache["k"].at[bidx, tgt].set(
                k.astype(cache["k"].dtype), mode="drop")
            cv = cache["v"].at[bidx, tgt].set(
                v.astype(cache["v"].dtype), mode="drop")
        elif jnp.ndim(idx) == 1:
            # per-slot write positions (serving engine): each row lands at
            # its own sequence offset
            upd = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, i, axis=0))
            ck = upd(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), idx)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "idx": idx + S}
        k, v = ck.astype(dt), cv.astype(dt)
        kv_len = idx + S
        q_offset = idx
        causal = True

    q = shard(q, "bshd")
    k = shard(k, "bskv")
    v = shard(v, "bskv")
    out = attention(q, k, v, causal=causal and kv_src is None,
                    q_offset=q_offset, kv_len=kv_len, kv_valid=kv_valid)
    out = out.reshape(B, S, H * D)
    out = out @ p["wo"]["w"].astype(dt)
    return shard(out, "btd"), new_cache


# ----------------------------------------------------------------------
# LM head with shard-local gradients.
#
# GSPMD's default plan for the head-matmul backward all-gathers the full
# (B,S,V) cotangent over the vocab axis before forming d_embed (observed:
# 40 GB/device at qwen3 scale). The gradient contractions are expressible
# entirely shard-local (+ a small all-reduce), so we write the vjp by hand
# with explicit constraints. w: (V, d) vocab-major (the embedding table
# itself when tied).
# ----------------------------------------------------------------------
@jax.custom_vjp
def lm_head(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,vd->bsv", x, w)


def _lm_head_fwd(x, w):
    return lm_head(x, w), (x, w)


def _lm_head_bwd(res, g):
    x, w = res
    g = shard(g, "btv")
    dx = shard(jnp.einsum("bsv,vd->bsd", g, w), "btd")
    dw = shard(jnp.einsum("bsv,bsd->vd", g, x.astype(g.dtype)), "head_w")
    return dx.astype(x.dtype), dw.astype(w.dtype)


lm_head.defvjp(_lm_head_fwd, _lm_head_bwd)


# ----------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------
def decl_mlp(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    decl = {
        "up": P.linear(d, f, "embed", "ffn"),
        "down": P.linear(f, d, "ffn", "embed"),
    }
    if cfg.gated_mlp:
        decl["gate"] = P.linear(d, f, "embed", "ffn")
    return decl


def apply_mlp(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = x @ p["up"]["w"].astype(dt)
    if cfg.gated_mlp:
        g = x @ p["gate"]["w"].astype(dt)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, "btf")
    return shard(h @ p["down"]["w"].astype(dt), "btd")


# ----------------------------------------------------------------------
# Standard decoder block: (rmsnorm -> attn -> +res) (rmsnorm -> mlp -> +res)
# ----------------------------------------------------------------------
def decl_dense_block(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": P.norm(cfg.d_model),
        "attn": decl_attention(cfg),
        "ln2": P.norm(cfg.d_model),
        "mlp": decl_mlp(cfg),
    }


def apply_dense_block(p, cfg: ModelConfig, x, *, causal=True, cache=None,
                      positions=None, use_rope=True, spec=None,
                      kv_valid=None):
    h, new_cache = apply_attention(
        p["attn"], cfg, apply_rmsnorm(p["ln1"], x, cfg.norm_eps),
        causal=causal, cache=cache, positions=positions, use_rope=use_rope,
        spec=spec, kv_valid=kv_valid)
    x = x + h
    x = x + apply_mlp(p["mlp"], cfg, apply_rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x, new_cache


# Cross-attention block (VLM image layers / enc-dec decoder cross part).
def decl_xattn_block(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": P.norm(cfg.d_model),
        "xattn": decl_attention(cfg, cross=True),
        "gate_attn": P.ParamDecl((), (), "zeros"),
        "ln2": P.norm(cfg.d_model),
        "mlp": decl_mlp(cfg),
        "gate_mlp": P.ParamDecl((), (), "zeros"),
    }


def apply_xattn_block(p, cfg: ModelConfig, x, kv_src):
    h, _ = apply_attention(
        p["xattn"], cfg, apply_rmsnorm(p["ln1"], x, cfg.norm_eps),
        kv_src=kv_src, causal=False, use_rope=False)
    x = x + jnp.tanh(p["gate_attn"].astype(x.dtype)) * h
    h = apply_mlp(p["mlp"], cfg, apply_rmsnorm(p["ln2"], x, cfg.norm_eps))
    x = x + jnp.tanh(p["gate_mlp"].astype(x.dtype)) * h
    return x

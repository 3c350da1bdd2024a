"""Core transformer layers: norms, RoPE, GQA attention (qk-norm, cross-attn,
KV-cache decode), gated/plain MLP. Functional style: ``decl_*`` builds the
parameter declaration tree, ``apply_*`` consumes the materialized params.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
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
# Attention (GQA; `repro.kernels.ops` picks the implementation)
# ----------------------------------------------------------------------
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

    if cache is None or kv_src is not None:
        out = ops.attention(shard(q, "bshd"), shard(k, "bskv"),
                            shard(v, "bskv"), causal=causal and kv_src is None,
                            kv_valid=kv_valid)
        new_cache = None
    elif "pt" in cache:
        # block-paged cache (serve/kv_cache.py): pool (P,page,Hkv,D),
        # page table (B,M), per-slot positions (B,). Stores scatter
        # through the table (out-of-table/idle writes DROP — no dead
        # rewrites); reads gather the logical view back, masked where
        # the table is unmapped. With a "layer" entry the pools are the
        # decode scan's layer-stacked (L,P,page,Hkv,D) carry, read and
        # written in place at that layer.
        idx, pt = cache["idx"], cache["pt"]
        counters = "kcnt" in cache
        kw = dict(layer=cache.get("layer"), counters=counters)
        if spec == "defer":
            # speculative verify, rollback mode: the pool is NOT written.
            # Attention runs over the committed history with the verify
            # window spliced in at its positions — pure activation
            # memory — and the window K/V ride in win_k/win_v for
            # LM.commit_verify to scatter only the accepted prefix.
            # Values round-trip through the pool dtype exactly like the
            # scatter-then-gather path, so the logits are bit-identical
            # to overwrite mode; cnt is all-zero (no stores).
            out, _, _, cnt = ops.paged_window(
                q, k, v, cache["k"], cache["v"], pt, idx, store=False, **kw)
            new_cache = {**cache, "idx": idx + S, "win_k": k, "win_v": v}
        else:
            if S == 1:
                out, ck, cv, cnt = ops.paged_decode(
                    q, k, v, cache["k"], cache["v"], pt, idx, **kw)
            else:
                # prefill chunk / verify-overwrite window
                out, ck, cv, cnt = ops.paged_window(
                    q, k, v, cache["k"], cache["v"], pt, idx, store=True,
                    verify=spec == "overwrite", **kw)
            new_cache = {**cache, "k": ck, "v": cv, "idx": idx + S}
        if counters:
            new_cache["kcnt"] = cnt
    elif jnp.ndim(cache["idx"]) == 0:
        # one write position for the whole batch
        idx = cache["idx"]
        out, ck, cv = ops.cached_decode(q, k, v, cache["k"], cache["v"], idx)
        new_cache = {"k": ck, "v": cv, "idx": idx + S}
    else:
        # per-slot write positions (serving engine): each row lands at
        # its own sequence offset
        idx = cache["idx"]
        if spec is not None:
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
        else:
            upd = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, i, axis=0))
            ck = upd(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), idx)
        new_cache = {"k": ck, "v": cv, "idx": idx + S}
        k, v = ck.astype(dt), cv.astype(dt)
        kv_len = idx + S
        out = ops.attention(shard(q, "bshd"), shard(k, "bskv"),
                            shard(v, "bskv"), causal=True, q_offset=idx,
                            kv_len=kv_len, kv_valid=kv_valid)
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

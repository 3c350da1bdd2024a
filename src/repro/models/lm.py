"""Decoder LM assembly: heterogeneous block schedules compiled into
scan-over-superblocks so the HLO is O(1) in network depth.

A *superblock* is the repeating pattern unit of an architecture:
  dense/moe : 1 block
  vlm       : (period-1) dense + 1 cross-attn block        (llama-3.2-vision)
  hybrid    : `attn_period` mamba + 1 SHARED attn block    (zamba2)
  ssm       : (slstm_period-1) mLSTM + 1 sLSTM             (xlstm)
  audio     : separate encoder scan + decoder scan          (whisper)

Shared blocks (zamba2's attention) have ONE parameter set closed over the
scan — faithful to the published weight sharing — while their KV caches are
per-invocation (stacked, carried through the scan like all other caches).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import params as P
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as SSM
from repro.models import xlstm as XL
from repro.sharding.ctx import shard


def _mask_pad_vocab(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """-inf the padded vocab tail so sampling/eval never selects it."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad = logits.shape[-1] - cfg.vocab_size
    neg = jnp.full(logits.shape[:-1] + (pad,), -1e30, logits.dtype)
    return jnp.concatenate([logits[..., :cfg.vocab_size], neg], axis=-1)


# ----------------------------------------------------------------------
# Schedule
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class Schedule:
    pattern: Tuple[str, ...]      # sub-block types within one superblock
    n_super: int
    tail: Tuple[str, ...] = ()    # leftover blocks appended after the scan
    has_shared: bool = False
    has_encoder: bool = False


def make_schedule(cfg: ModelConfig) -> Schedule:
    if cfg.family == "dense":
        return Schedule(("dense",), cfg.num_layers)
    if cfg.family == "moe":
        return Schedule(("moe",), cfg.num_layers)
    if cfg.family == "vlm":
        p = cfg.cross_attn_period
        assert cfg.num_layers % p == 0, "vlm layers must divide the period"
        return Schedule(("dense",) * (p - 1) + ("xattn",), cfg.num_layers // p)
    if cfg.family == "hybrid":
        p = cfg.attn_period
        n, r = divmod(cfg.num_layers, p)
        return Schedule(("mamba",) * p + ("shared",), n,
                        tail=("mamba",) * r, has_shared=True)
    if cfg.family == "ssm":
        sp = cfg.xlstm.slstm_period
        assert cfg.num_layers % sp == 0
        return Schedule(("mlstm",) * (sp - 1) + ("slstm",), cfg.num_layers // sp)
    if cfg.family == "audio":
        return Schedule(("encdec",), cfg.num_layers, has_encoder=True)
    raise ValueError(cfg.family)


# ----------------------------------------------------------------------
# Sub-block declarations / applications
# ----------------------------------------------------------------------
def decl_moe_block(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": P.norm(cfg.d_model),
        "attn": L.decl_attention(cfg),
        "ln2": P.norm(cfg.d_model),
        "moe": M.decl_moe(cfg),
    }


def decl_encdec_block(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": P.norm(cfg.d_model),
        "attn": L.decl_attention(cfg),
        "lnx": P.norm(cfg.d_model),
        "xattn": L.decl_attention(cfg, cross=True),
        "ln2": P.norm(cfg.d_model),
        "mlp": L.decl_mlp(cfg),
    }


def _decl_sub(cfg: ModelConfig, typ: str) -> Dict[str, Any]:
    if typ == "dense":
        return L.decl_dense_block(cfg)
    if typ == "moe":
        return decl_moe_block(cfg)
    if typ == "xattn":
        return L.decl_xattn_block(cfg)
    if typ == "mamba":
        return SSM.decl_mamba(cfg)
    if typ == "mlstm":
        return XL.decl_mlstm(cfg)
    if typ == "slstm":
        return XL.decl_slstm(cfg)
    if typ == "encdec":
        return decl_encdec_block(cfg)
    if typ == "shared":
        return {}                     # params live outside the scan
    raise ValueError(typ)


def decl_superblock(cfg: ModelConfig, pattern) -> Dict[str, Any]:
    return {f"b{i}_{t}": _decl_sub(cfg, t) for i, t in enumerate(pattern)
            if t != "shared"}


# ----------------------------------------------------------------------
# Caches / states
# ----------------------------------------------------------------------
def _init_sub_cache(cfg: ModelConfig, typ: str, batch: int, max_len: int,
                    kv_dtype) -> Any:
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    if typ in ("dense", "moe", "shared", "encdec"):
        c = {"k": jnp.zeros((batch, max_len, Hkv, D), kv_dtype),
             "v": jnp.zeros((batch, max_len, Hkv, D), kv_dtype),
             "idx": jnp.zeros((), jnp.int32)}
        if typ == "encdec":
            c["xk"] = jnp.zeros((batch, cfg.encoder_frames, Hkv, D), kv_dtype)
            c["xv"] = jnp.zeros((batch, cfg.encoder_frames, Hkv, D), kv_dtype)
        return c
    if typ == "xattn":
        return {"xk": jnp.zeros((batch, cfg.num_image_tokens, Hkv, D), kv_dtype),
                "xv": jnp.zeros((batch, cfg.num_image_tokens, Hkv, D), kv_dtype)}
    if typ == "mamba":
        return SSM.init_mamba_state(cfg, batch, kv_dtype)
    if typ == "mlstm":
        return XL.init_mlstm_state(cfg, batch)
    if typ == "slstm":
        return XL.init_slstm_state(cfg, batch)
    raise ValueError(typ)


# Parameter leaves the forward reads only through ``.astype(cfg.dtype)``
# (LM.compute_params): linear ``w``, MoE expert stacks, embedding, head.
_COMPUTE_LEAVES = frozenset({"w", "w_up", "w_gate", "w_down", "embed",
                             "head"})


def _stack_cache(tree, n: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


# ----------------------------------------------------------------------
# Model
# ----------------------------------------------------------------------
class LM:
    """Functional LM: holds config + schedule, params passed explicitly."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.sched = make_schedule(cfg)
        # activation checkpointing for the scanned superblock:
        #   "none" | "full" | "dots"  (set by the train-step factory)
        self.remat = "none"

    def _maybe_remat(self, fn):
        if self.remat == "full":
            return jax.checkpoint(fn, prevent_cse=False)
        if self.remat == "dots":
            return jax.checkpoint(
                fn, prevent_cse=False,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return fn

    # -------------------------- declarations -------------------------
    def decl(self) -> Dict[str, Any]:
        cfg, sch = self.cfg, self.sched
        d = {
            "embed": P.ParamDecl((cfg.padded_vocab, cfg.d_model),
                                 ("vocab", "embed"), "normal", 0.02),
            "final_norm": P.norm(cfg.d_model),
            "main": P.stack_decls(decl_superblock(cfg, sch.pattern), sch.n_super),
        }
        if not cfg.tie_embeddings:
            # vocab-major (V, d) so the lm_head vjp is transpose-free
            d["head"] = P.ParamDecl((cfg.padded_vocab, cfg.d_model),
                                    ("vocab", "embed"), "normal",
                                    1.0 / (cfg.d_model ** 0.5))
        if sch.tail:
            d["tail"] = P.stack_decls(_decl_sub(cfg, sch.tail[0]), len(sch.tail))
        if sch.has_shared:
            d["shared"] = L.decl_dense_block(cfg)
        if sch.has_encoder:
            d["enc"] = {
                "blocks": P.stack_decls(L.decl_dense_block(cfg), cfg.encoder_layers),
                "norm": P.norm(cfg.d_model),
            }
        return d

    def init(self, key: jax.Array, dtype=None) -> Any:
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return P.init_tree(key, self.decl(), dtype)

    def abstract_params(self, dtype=None) -> Any:
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return P.abstract_tree(self.decl(), dtype)

    def param_axes(self) -> Any:
        return P.axes_tree(self.decl())

    def decode_params(self, params) -> Any:
        """The decode-path view of ``params``.

        The encoder tower and the cross-attention K/V projections (and
        their k_norm) only feed ``init_cache``'s cross-KV precompute;
        ``decode_step``/``prefill``/``verify`` read the cached ``xk``/
        ``xv`` instead. Handing the full tree to a traced decode step
        leaves those leaves as dead jaxpr invars (tier-0 dead_param) and
        ships dead bytes to the device on a real serving host. Families
        without cross-attention get ``params`` back unchanged.
        """
        sch = self.sched
        xattn_blocks = [f"b{i}_{t}" for i, t in enumerate(sch.pattern)
                        if t in ("xattn", "encdec")]
        if not xattn_blocks and not sch.has_encoder:
            return params
        out = dict(params)
        if sch.has_encoder:
            out.pop("enc", None)
        if xattn_blocks:
            main = dict(out["main"])
            for name in xattn_blocks:
                blk = dict(main[name])
                blk["xattn"] = {k: v for k, v in blk["xattn"].items()
                                if k not in ("wk", "wv", "k_norm")}
                main[name] = blk
            out["main"] = main
        return out

    def compute_params(self, params) -> Any:
        """``params`` with every leaf the forward reads only as
        ``w.astype(cfg.dtype)`` already in ``cfg.dtype``.

        Those leaves are the projection matrices (``w``), the MoE expert
        stacks (``w_up``/``w_gate``/``w_down``), the embedding and an
        untied head. The matmuls then get the same inputs, and the cast
        is no longer redone inside every jitted decode tick and prefill.
        Norm scales and the MoE router are read at float32 and stay as
        given. A leaf already in ``cfg.dtype`` comes back as the same
        array, so an already-cast tree is returned leaf for leaf.
        """
        dt = jnp.dtype(self.cfg.dtype)

        def cast(path, leaf):
            if (getattr(path[-1], "key", None) in _COMPUTE_LEAVES
                    and leaf.dtype != dt):
                return leaf.astype(dt)
            return leaf
        return jax.tree_util.tree_map_with_path(cast, params)

    # ----------------------------- encoder ---------------------------
    def encode(self, params, frames: jax.Array,
               frame_lengths: Optional[jax.Array] = None) -> jax.Array:
        """audio/whisper encoder over stubbed frame embeddings (B,F,d).

        ``frame_lengths``: optional (B,) true frame counts for
        right-padded inputs. Padded keys are masked out of every
        encoder self-attention, so rows below each true length are
        independent of how far the batch was padded — the invariant
        that lets serving bucket the encoder extent (launch/serve.py)
        instead of always padding to cfg.encoder_frames.
        """
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.dtype))
        valid = None
        if frame_lengths is not None:
            valid = (jnp.arange(x.shape[1])[None, :]
                     < jnp.asarray(frame_lengths, jnp.int32)[:, None])

        def blk(x, p):
            y, _ = L.apply_dense_block(p, cfg, x, causal=False,
                                       use_rope=True, kv_valid=valid)
            return y, None
        x, _ = jax.lax.scan(blk, x, params["enc"]["blocks"])
        return L.apply_rmsnorm(params["enc"]["norm"], x, cfg.norm_eps)

    # ----------------------------- forward ---------------------------
    def backbone(self, params, tokens: jax.Array, *,
                 img: Optional[jax.Array] = None,
                 frames: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
        """Everything up to (and incl.) the final norm: (hidden, moe_aux)."""
        cfg, sch = self.cfg, self.sched
        dt = jnp.dtype(cfg.dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        x = shard(x, "btd")

        enc_out = None
        if sch.has_encoder:
            assert frames is not None, "audio family needs frame embeddings"
            enc_out = self.encode(params, frames)
        if cfg.family == "vlm":
            assert img is not None, "vlm family needs image patch embeddings"
            img = img.astype(dt)

        def superblock(carry, p_layer):
            x, aux = carry
            for i, typ in enumerate(sch.pattern):
                name = f"b{i}_{typ}"
                if typ == "dense":
                    x, _ = L.apply_dense_block(p_layer[name], cfg, x)
                elif typ == "moe":
                    blk = p_layer[name]
                    h, _ = L.apply_attention(
                        blk["attn"], cfg,
                        L.apply_rmsnorm(blk["ln1"], x, cfg.norm_eps))
                    x = x + h
                    h, a = M.apply_moe(
                        blk["moe"], cfg,
                        L.apply_rmsnorm(blk["ln2"], x, cfg.norm_eps))
                    x = x + h
                    aux = aux + a
                elif typ == "xattn":
                    x = L.apply_xattn_block(p_layer[name], cfg, x, img)
                elif typ == "mamba":
                    x, _ = SSM.apply_mamba(p_layer[name], cfg, x)
                elif typ == "mlstm":
                    x, _ = XL.apply_mlstm(p_layer[name], cfg, x)
                elif typ == "slstm":
                    x, _ = XL.apply_slstm(p_layer[name], cfg, x)
                elif typ == "shared":
                    x, _ = L.apply_dense_block(params["shared"], cfg, x)
                elif typ == "encdec":
                    blk = p_layer[name]
                    h, _ = L.apply_attention(
                        blk["attn"], cfg,
                        L.apply_rmsnorm(blk["ln1"], x, cfg.norm_eps))
                    x = x + h
                    h, _ = L.apply_attention(
                        blk["xattn"], cfg,
                        L.apply_rmsnorm(blk["lnx"], x, cfg.norm_eps),
                        kv_src=enc_out, causal=False, use_rope=False)
                    x = x + h
                    x = x + L.apply_mlp(
                        blk["mlp"], cfg,
                        L.apply_rmsnorm(blk["ln2"], x, cfg.norm_eps))
                else:
                    raise ValueError(typ)
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(self._maybe_remat(superblock),
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["main"])
        if sch.tail:
            def tailblk(x, p):
                y, _ = SSM.apply_mamba(p, cfg, x)
                return y, None
            x, _ = jax.lax.scan(tailblk, x, params["tail"])

        x = L.apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return x, aux

    def head_weight(self, params) -> jax.Array:
        """(V_padded, d) vocab-major head weight (embedding when tied)."""
        return (params["embed"] if self.cfg.tie_embeddings
                else params["head"])

    def forward(self, params, tokens: jax.Array, *,
                img: Optional[jax.Array] = None,
                frames: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
        """Train/prefill forward. Returns (logits, moe_aux_loss)."""
        cfg = self.cfg
        x, aux = self.backbone(params, tokens, img=img, frames=frames)
        w = self.head_weight(params)
        logits = L.lm_head(x, w.astype(x.dtype))
        logits = _mask_pad_vocab(logits, cfg)
        return shard(logits, "btv"), aux

    def loss(self, params, batch: Dict[str, Any], *,
             z_loss: float = 0.0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Fused vocab-parallel LM loss (never materializes global logits)."""
        from repro.sharding.ctx import current_sharder
        from repro.train.fused_xent import lm_loss
        x, aux = self.backbone(params, batch["tokens"],
                               img=batch.get("img"),
                               frames=batch.get("frames"))
        w = self.head_weight(params)
        nll = lm_loss(x, w.astype(x.dtype), batch["labels"],
                      z_loss=z_loss, sharder=current_sharder())
        return nll + aux, {"nll": nll, "moe_aux": aux}

    # ------------------------------ decode ---------------------------
    def init_cache(self, params, batch: int, max_len: int, *,
                   img: Optional[jax.Array] = None,
                   frames: Optional[jax.Array] = None,
                   frame_lengths: Optional[jax.Array] = None,
                   kv_dtype=jnp.bfloat16) -> Any:
        """Preallocate decode caches; precompute cross-attn KV.

        ``frame_lengths``: (B,) true frame counts when ``frames`` is
        right-padded (and possibly bucketed below cfg.encoder_frames).
        The encoder masks padded keys and the cross-KV cache carries an
        ``xvalid`` mask so decode cross-attention ignores them too —
        greedy outputs are then independent of the padded extent."""
        cfg, sch = self.cfg, self.sched
        main = {}
        for i, typ in enumerate(sch.pattern):
            sub = _init_sub_cache(cfg, typ, batch, max_len, kv_dtype)
            main[f"b{i}_{typ}"] = sub
        cache = {"main": _stack_cache(main, sch.n_super)}
        if sch.tail:
            tail = _init_sub_cache(cfg, sch.tail[0], batch, max_len, kv_dtype)
            cache["tail"] = _stack_cache(tail, len(sch.tail))

        # Precompute cross-attention K/V (vlm images / encdec encoder out).
        if cfg.family == "vlm" and img is not None:
            cache = self._fill_cross_kv(params, cache, img.astype(jnp.dtype(cfg.dtype)),
                                        "xattn", "xattn")
        if sch.has_encoder and frames is not None:
            enc_out = self.encode(params, frames, frame_lengths)
            cache = self._fill_cross_kv(params, cache, enc_out, "encdec",
                                        "xattn", src_lengths=frame_lengths)
        return cache

    def _fill_cross_kv(self, params, cache, src, typ, attn_key,
                       src_lengths=None):
        """Compute per-layer cross KV from src for all scanned layers."""
        cfg, sch = self.cfg, self.sched
        Hkv, D = cfg.num_kv_heads, cfg.head_dim
        B, Skv = src.shape[:2]
        valid = None
        if src_lengths is not None:
            valid = (jnp.arange(Skv)[None, :]
                     < jnp.asarray(src_lengths, jnp.int32)[:, None])
        for i, t in enumerate(sch.pattern):
            if t != typ:
                continue
            name = f"b{i}_{t}"
            blk_p = params["main"][name]
            ap = blk_p[attn_key] if attn_key in blk_p else blk_p["xattn"]

            def kv_of(p_attn, x):
                k = (x @ p_attn["wk"]["w"].astype(x.dtype)).reshape(B, Skv, Hkv, D)
                v = (x @ p_attn["wv"]["w"].astype(x.dtype)).reshape(B, Skv, Hkv, D)
                if cfg.qk_norm:
                    k = L.apply_rmsnorm(p_attn["k_norm"], k, cfg.norm_eps)
                return k, v
            # vmap over the stacked layer dim
            ks, vs = jax.vmap(kv_of, in_axes=(0, None))(ap, src)
            sub = dict(cache["main"][name])
            sub["xk"] = ks.astype(sub["xk"].dtype)
            sub["xv"] = vs.astype(sub["xv"].dtype)
            if valid is not None:
                sub["xvalid"] = jnp.broadcast_to(
                    valid, (self.sched.n_super,) + valid.shape)
            cache["main"][name] = sub
        return cache

    # ------------------------- paged cache ---------------------------
    def init_paged_cache(self, params, num_slots: int, max_len: int, *,
                         page_size: int = 16,
                         num_pages: Optional[int] = None,
                         kv_dtype=jnp.bfloat16,
                         kernel_counters: bool = False) -> Any:
        """Block-paged decode cache (serve/kv_cache.py): per layer, one
        flat pool of `num_pages` pages of `page_size` K/V rows shared by
        all slots, plus a per-slot page table mapping logical positions
        to pages (-1 = unmapped) and per-slot write indices. Families
        whose every sub-block carries an indexed KV cache only (the
        serving-engine families).

        ``kernel_counters=True`` adds a per-layer ``kcnt`` leaf
        ((num_slots, 3) int32 [stored, silent, dropped] element counts)
        that every paged attention forward overwrites with its
        store-site waste counters (DESIGN.md § Kernel tier); its
        presence is the trace-time enable switch, and the leaf rides
        the decode scan so layers stack automatically."""
        cfg, sch = self.cfg, self.sched
        Hkv, D = cfg.num_kv_heads, cfg.head_dim
        max_pages = -(-max_len // page_size)
        if num_pages is None:
            num_pages = num_slots * max_pages
        main = {}
        for i, typ in enumerate(sch.pattern):
            if typ not in ("dense", "moe"):
                raise ValueError(
                    f"paged cache needs indexed KV in every sub-block; "
                    f"{typ!r} blocks are unsupported")
            sub = {
                "k": jnp.zeros((num_pages, page_size, Hkv, D), kv_dtype),
                "v": jnp.zeros((num_pages, page_size, Hkv, D), kv_dtype),
                "idx": jnp.zeros((num_slots,), jnp.int32),
                "pt": jnp.full((num_slots, max_pages), -1, jnp.int32),
            }
            if kernel_counters:
                sub["kcnt"] = jnp.zeros((num_slots, 3), jnp.int32)
            main[f"b{i}_{typ}"] = sub
        return {"main": _stack_cache(main, sch.n_super)}

    @staticmethod
    def kernel_counters(cache) -> Optional[Dict[str, jax.Array]]:
        """The kernel-tier waste counters of the last paged forward, per
        sub-block name: (n_layers, num_slots, 3) int32 stacked over the
        scanned layers — or None when the cache was built without
        ``kernel_counters=True``."""
        out = {name: sub["kcnt"] for name, sub in cache["main"].items()
               if "kcnt" in sub}
        return out or None

    @staticmethod
    def cache_is_paged(cache) -> bool:
        return any("pt" in sub for sub in cache["main"].values())

    def with_page_table(self, cache, pt) -> Any:
        """Return `cache` with every paged KV sub-block's page table
        replaced by `pt` ((num_slots, max_pages) int32, -1 = unmapped)."""
        pt = jnp.asarray(pt, jnp.int32)

        def set_in(tree, n):
            return {name: ({**sub,
                            "pt": jnp.broadcast_to(pt, (n,) + pt.shape)}
                           if "pt" in sub else sub)
                    for name, sub in tree.items()}

        new = dict(cache)
        new["main"] = set_in(cache["main"], self.sched.n_super)
        return new

    # ------------------------- cache index --------------------------
    def cache_index(self, cache) -> jax.Array:
        """Current write index of the decode cache: scalar, or (B,) when
        the cache has per-slot positions (serving engine)."""
        for sub in cache["main"].values():
            if "idx" in sub:
                return sub["idx"][0]
        raise ValueError("cache has no indexed KV sub-block")

    def with_cache_index(self, cache, idx) -> Any:
        """Return `cache` with every KV sub-block's write index replaced
        by `idx` (scalar, or (B,) for per-slot serving positions)."""
        idx = jnp.asarray(idx, jnp.int32)

        def set_in(tree, n):
            out = {}
            for name, sub in tree.items():
                if "idx" in sub:
                    sub = {**sub,
                           "idx": jnp.broadcast_to(idx, (n,) + idx.shape)}
                out[name] = sub
            return out

        new = dict(cache)
        new["main"] = set_in(cache["main"], self.sched.n_super)
        if "tail" in cache:
            new["tail"] = set_in(cache["tail"], len(self.sched.tail))
        return new

    # ------------------------------ prefill --------------------------
    def prefill(self, params, cache, tokens: jax.Array, *,
                lengths: Optional[jax.Array] = None) -> Tuple[jax.Array, Any]:
        """Single-pass batched cache fill: one forward through the
        decode/cache path over the whole prompt instead of `prompt_len`
        sequential decode steps.

        tokens: (B, P) prompt tokens, right-padded when lengths vary;
        lengths: optional (B,) true prompt lengths. Writes K/V for all P
        positions of every row in one call; with `lengths` the cache's
        write index is set per-row so padded tail positions (whose K/V
        are garbage — dead stores by construction) are masked out and
        overwritten as decode advances. Returns (logits (B,P,V), cache).
        Per-position K/V depend only on the causal prefix, so entries
        below each row's true length are exactly the token-by-token
        values (bit-identical on the shared fallback attention path).
        """
        logits, cache = self.decode_step(params, cache, tokens)
        if lengths is not None:
            cache = self.with_cache_index(
                cache, jnp.asarray(lengths, jnp.int32))
        return logits, cache

    # ------------------------- speculative verify --------------------
    def verify(self, params, cache, tokens: jax.Array, *,
               commit: bool = True) -> Tuple[jax.Array, Any]:
        """Width-k speculative verification forward.

        tokens: (B, W) = [last accepted token, draft_1 .. draft_{W-1}]
        at each slot's own cache offset (the engine's per-slot (B,)
        write index). One call yields the logits of all W positions —
        position j attends the committed history plus window rows <= j —
        so the greedy acceptance chain and the bonus token come out of a
        single forward instead of W sequential decode steps.

        commit=True ("overwrite"): all W K/V rows are stored through the
        normal cache path (bounded: rows past the extent drop). Rows
        past the accept point become Def.-1 dead stores — the waste
        `ServingDetectors.rejected_draft_store` measures. commit=False
        ("defer", paged caches only): the pool is untouched and each
        sub-block returns the window K/V as ``win_k``/``win_v``; pair
        with `commit_verify` to scatter only the accepted prefix
        (rollback — the measured waste, eliminated).
        """
        return self.decode_step(params, cache, tokens,
                                spec="overwrite" if commit else "defer")

    def commit_verify(self, cache, start: jax.Array,
                      length: jax.Array) -> Any:
        """Scatter a deferred verify window's accepted prefix into the
        paged pool: rows [0, length[b]) of each sub-block's win_k/win_v
        land at logical positions start[b]+s through the page table
        (length 0 = idle slot, nothing stored). Drops the win_* leaves.
        """
        def one(sub):
            if "win_k" not in sub:
                return sub
            def upd(pk, pv, wk, wv, pt):
                return ops.paged_update(pk, pv, wk, wv, pt, start,
                                        length=length)
            nk, nv = jax.vmap(upd)(sub["k"], sub["v"], sub["win_k"],
                                   sub["win_v"], sub["pt"])
            out = {n: v for n, v in sub.items()
                   if n not in ("win_k", "win_v")}
            if "kcnt" in sub:
                # kernel tier: the commit scatter is where the rollback
                # path's machine-level stores happen — count them here so
                # rejected_draft_store is exactly 0 (only accepted rows
                # are ever stored).
                def cnt(pk, pv, wk, wv, pt):
                    return ops.paged_store_counts(
                        pk, pv, wk, wv, pt, start, length=length,
                        tol=ops.COUNTER_TOL)
                out["kcnt"] = jax.vmap(cnt)(sub["k"], sub["v"],
                                            sub["win_k"], sub["win_v"],
                                            sub["pt"])
            out["k"], out["v"] = nk, nv
            return out

        new = dict(cache)
        new["main"] = {name: one(sub) for name, sub in cache["main"].items()}
        return new

    def decode_step(self, params, cache, tokens: jax.Array, *,
                    spec: Optional[str] = None) -> Tuple[jax.Array, Any]:
        """One decode step. tokens: (B, S). Returns (logits, new_cache).

        ``spec`` marks a speculative width-k verify forward (see
        `verify`); it only reaches the indexed-KV sub-blocks the serving
        engine drives (dense/moe)."""
        cfg, sch = self.cfg, self.sched
        dt = jnp.dtype(cfg.dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        x = shard(x, "btd_dec")

        def superblock(x, inp):
            p_layer, c_layer = inp
            new_c = {}
            for i, typ in enumerate(sch.pattern):
                name = f"b{i}_{typ}"
                c = c_layer[name]
                if typ == "dense":
                    x, nc = L.apply_dense_block(p_layer[name], cfg, x,
                                                cache=c, spec=spec)
                elif typ == "moe":
                    blk = p_layer[name]
                    h, nc = L.apply_attention(
                        blk["attn"], cfg,
                        L.apply_rmsnorm(blk["ln1"], x, cfg.norm_eps),
                        cache=c, spec=spec)
                    x = x + h
                    h, _ = M.apply_moe(
                        blk["moe"], cfg,
                        L.apply_rmsnorm(blk["ln2"], x, cfg.norm_eps))
                    x = x + h
                elif typ == "xattn":
                    blk = p_layer[name]
                    h = L.apply_rmsnorm(blk["ln1"], x, cfg.norm_eps)
                    h = self._cached_xattn(blk["xattn"], h, c)
                    x = x + jnp.tanh(blk["gate_attn"].astype(x.dtype)) * h
                    h = L.apply_mlp(blk["mlp"], cfg,
                                    L.apply_rmsnorm(blk["ln2"], x, cfg.norm_eps))
                    x = x + jnp.tanh(blk["gate_mlp"].astype(x.dtype)) * h
                    nc = c
                elif typ == "mamba":
                    x, nc = SSM.apply_mamba(p_layer[name], cfg, x, state=c)
                elif typ == "mlstm":
                    x, nc = XL.apply_mlstm(p_layer[name], cfg, x, state=c)
                elif typ == "slstm":
                    x, nc = XL.apply_slstm(p_layer[name], cfg, x, state=c)
                elif typ == "shared":
                    x, nc = L.apply_dense_block(params["shared"], cfg, x, cache=c)
                elif typ == "encdec":
                    blk = p_layer[name]
                    h, nc = L.apply_attention(
                        blk["attn"], cfg,
                        L.apply_rmsnorm(blk["ln1"], x, cfg.norm_eps), cache=c)
                    x = x + h
                    h = L.apply_rmsnorm(blk["lnx"], x, cfg.norm_eps)
                    h = self._cached_xattn(blk["xattn"], h, c)
                    x = x + h
                    x = x + L.apply_mlp(blk["mlp"], cfg,
                                        L.apply_rmsnorm(blk["ln2"], x, cfg.norm_eps))
                    nc = {**nc, "xk": c["xk"], "xv": c["xv"]}
                    if "xvalid" in c:
                        nc["xvalid"] = c["xvalid"]
                else:
                    raise ValueError(typ)
                new_c[name] = nc
            return x, new_c

        x, new_main = self._scan_layers(superblock, x, params["main"],
                                        cache["main"])
        new_cache = {"main": new_main}
        if sch.tail:
            def tailblk(x, inp):
                p, c = inp
                y, nc = SSM.apply_mamba(p, cfg, x, state=c)
                return y, nc
            x, new_tail = jax.lax.scan(tailblk, x, (params["tail"], cache["tail"]))
            new_cache["tail"] = new_tail

        x = L.apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
        w = self.head_weight(params)
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(dt))
        return _mask_pad_vocab(logits, cfg), new_cache

    @staticmethod
    def _scan_layers(superblock, x, p_main, c_main):
        """Scan ``superblock`` over the stacked layers.

        A sub-cache with a page table (a paged KV pool) sends its
        stacked ``k``/``v`` pools through the loop carry with the layer
        counter, and each layer's attention reads and writes them in
        place at that layer (the sub-cache it sees holds the whole
        pools and ``"layer"``). Every other leaf — and every other
        sub-cache — rides xs/ys, sliced per layer. Carrying the pools
        keeps one pool buffer through the loop: as xs/ys each layer
        would slice its pool out, update a copy and write it back."""
        pooled = [n for n, sub in c_main.items() if "pt" in sub]
        pools = {n: {"k": c_main[n]["k"], "v": c_main[n]["v"]}
                 for n in pooled}
        xs = {n: ({k: a for k, a in sub.items() if k not in ("k", "v")}
                  if n in pools else sub) for n, sub in c_main.items()}

        def body(carry, inp):
            x, layer, pools = carry
            p_layer, c_layer = inp
            c_layer = {n: ({**c, **pools[n], "layer": layer}
                           if n in pools else c)
                       for n, c in c_layer.items()}
            x, new_c = superblock(x, (p_layer, c_layer))
            pools = {n: {"k": new_c[n]["k"], "v": new_c[n]["v"]}
                     for n in pools}
            ys = {n: ({k: a for k, a in c.items()
                       if k not in ("k", "v", "layer")}
                      if n in pools else c) for n, c in new_c.items()}
            return (x, layer + 1, pools), ys

        (x, _, pools), ys = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.int32), pools), (p_main, xs))
        return x, {n: ({**c, **pools[n]} if n in pools else c)
                   for n, c in ys.items()}

    def _cached_xattn(self, p_attn, x, c):
        """Cross-attention against precomputed cached KV."""
        cfg = self.cfg
        B, S, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        q = (x @ p_attn["wq"]["w"].astype(x.dtype)).reshape(B, S, H, D)
        if cfg.qk_norm:
            q = L.apply_rmsnorm(p_attn["q_norm"], q, cfg.norm_eps)
        # with "xvalid" (bucketed encoder prefill), keys past each row's
        # true source length never contribute
        out = ops.cross_attention(q, c["xk"], c["xv"],
                                  kv_valid=c.get("xvalid"))
        out = out.reshape(B, S, H * D)
        return out @ p_attn["wo"]["w"].astype(x.dtype)

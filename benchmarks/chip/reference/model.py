"""A plain float32 decoder in ``jax.numpy``, from the published equations.

Imports nothing of the program. It reads the benchmark's weights
(``weights.py``) by their layout and the configuration's ``model`` keys.
What it computes, per layer: RMSNorm, grouped-query attention with
optional per-head RMSNorm on q and k (Qwen3's qk-norm), rotary embeddings
on the two halves of each head (the HF ``rotate_half`` form), causal
softmax attention, a residual add, RMSNorm, and either a SwiGLU MLP or a
tanh-approximated GELU MLP, then a final RMSNorm and the head (the
embedding, when tied). Every matrix product runs at ``HIGHEST`` precision
(on a TPU a float32 product otherwise takes one bfloat16 pass).

Departures from the published models, the same in the program: RMSNorm
and no linear biases for StarCoder2 (which has LayerNorm and biases).

``mm`` is the matrix product of every linear layer and of the head; the
precision control (``check.py``) passes one that rounds its operands to
float8 first.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm_f32(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HI)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, pos, theta):
    """x: (..., S, H, D); pos: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal GQA. q: (S, H, D); k, v: (S, Hkv, D)."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(S, Hkv, H // Hkv, D)
    s = jnp.einsum("sjgd,tjd->jgst", qg, k, precision=HI) / math.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("jgst,tjd->sjgd", p, v, precision=HI)
    return o.reshape(S, H * D)


def _block(m: Dict, mm: Callable):
    H, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]

    def block(x, p):
        S = x.shape[0]
        pos = jnp.arange(S)
        a = p["attn"]
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        q = mm(h, a["wq"]["w"]).reshape(S, H, D)
        k = mm(h, a["wk"]["w"]).reshape(S, Hkv, D)
        v = mm(h, a["wv"]["w"]).reshape(S, Hkv, D)
        if m.get("qk_norm"):
            q = rmsnorm(q, a["q_norm"]["scale"], eps)
            k = rmsnorm(k, a["k_norm"]["scale"], eps)
        q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
        x = x + mm(attention(q, k, v), a["wo"]["w"])
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        f = p["mlp"]
        if m.get("gated_mlp", True):
            u = jax.nn.silu(mm(h, f["gate"]["w"])) * mm(h, f["up"]["w"])
        else:
            u = gelu_tanh(mm(h, f["up"]["w"]))
        return x + mm(u, f["down"]["w"]), None
    return block


def _layers(params):
    (sub,) = params["main"].values()      # one dense block per layer
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), sub)


def hidden(params, m: Dict, tokens, mm: Callable = mm_f32):
    """Final-normed hidden states (S, d) of one sequence."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(_block(m, mm), x, _layers(params))
    return rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])


def head(params, m: Dict):
    w = params["embed"] if m.get("tie_embeddings") else params["head"]
    return w[: m["vocab_size"]]


def logits(params, m: Dict, x, mm: Callable = mm_f32):
    """(rows, vocab_size) logits of hidden rows ``x``."""
    return mm(x, head(params, m).T)


def loss(params, m: Dict, tokens, labels, mm: Callable = mm_f32):
    """Mean next-token cross-entropy over every position of a batch."""
    @jax.checkpoint            # one row's activations live at a time
    def row(t, lab):
        lg = logits(params, m, hidden(params, m, t, mm), mm)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, lab[:, None], 1)[:, 0])
    tot = jax.lax.map(lambda tl: row(*tl), (tokens, labels))
    return jnp.sum(tot) / labels.size

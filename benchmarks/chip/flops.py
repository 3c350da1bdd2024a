"""Operations and bytes the algorithms need, computed from shapes.

These are the numerators of every roofline share and MFU: what the
mathematics of the call requires, not what the compiler emitted. A
multiply-add counts as two operations. Bytes are what must cross HBM at
least once: operands read and results written, each once.

``peak(kind)`` reads ``peaks.json``, keyed by ``device_kind``; a device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Sequence, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, pk: Dict[str, float]
               ) -> Tuple[float, str]:
    """Least time the chip needs, and which bound sets it."""
    tc = flops / pk["bf16_flops_per_s"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


# ------------------------------------------------------------ kernels
def paged_decode(ctx: Sequence[int], heads: int, kv_heads: int, dim: int,
                 pool_itemsize: int, act_itemsize: int = 2
                 ) -> Tuple[float, float]:
    """One ``paged_decode_attention`` call (one layer): for each live
    slot, one query row attends ``ctx`` keys (its history and the new
    row). Scores and the weighted sum take 2*D operations per (head,
    key) each. Bytes: the K and V rows attended, from the pool; the
    query, new K/V row and output, once."""
    keys = float(sum(ctx))
    flops = 4.0 * heads * dim * keys
    nbytes = (2.0 * kv_heads * dim * pool_itemsize * keys
              + len(ctx) * dim * (2 * heads + 2 * kv_heads) * act_itemsize)
    return flops, nbytes


def paged_window(rows: Iterable[Tuple[int, int]], heads: int,
                 kv_heads: int, dim: int, pool_itemsize: int,
                 act_itemsize: int = 2) -> Tuple[float, float]:
    """One ``paged_window_attention`` call (one layer) over admitted
    slots given as (start, length): ``length`` new query rows at
    positions start.. attend causally over the ``start`` cached rows and
    the window. Bytes: the cached prefix's K/V from the pool, the
    window's q/K/V and the output, once each."""
    flops = nbytes = 0.0
    for start, n in rows:
        keys = n * start + n * (n + 1) / 2.0      # sum over rows of keys
        flops += 4.0 * heads * dim * keys
        nbytes += (2.0 * kv_heads * dim * pool_itemsize * start
                   + n * dim * (2 * heads + 2 * kv_heads) * act_itemsize)
    return flops, nbytes


# --------------------------------------------------------------- model
def matmul_params(m: Dict) -> Dict[str, int]:
    """Weights that enter a matrix product per token, per layer and in
    the head (the embedding lookup is a gather, not a product)."""
    d, H, Hkv, D, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    mlp = (3 if m.get("gated_mlp", True) else 2) * d * F
    layer = d * H * D * 2 + d * Hkv * D * 2 + mlp
    return {"layer": layer, "head": d * m["vocab_size"]}


def decode_token_flops(m: Dict, ctx: float) -> float:
    """Forward operations for one decoded token at context ``ctx``:
    every weight once (2 per multiply-add), and attention over ``ctx``
    keys in every layer."""
    p = matmul_params(m)
    L = m["num_layers"]
    return (2.0 * (L * p["layer"] + p["head"])
            + L * 4.0 * m["num_heads"] * m["head_dim"] * ctx)


def prefill_flops(m: Dict, start: int, n: int) -> float:
    """Forward operations to admit one prompt suffix of ``n`` rows after
    ``start`` cached rows: every layer weight once per row, causal
    attention over each row's keys, and the head once (only the last
    row's logits give the first token)."""
    p = matmul_params(m)
    L = m["num_layers"]
    keys = n * start + n * (n + 1) / 2.0
    return (2.0 * n * L * p["layer"] + 2.0 * p["head"]
            + L * 4.0 * m["num_heads"] * m["head_dim"] * keys)


def train_token_flops(m: Dict, seq: int) -> float:
    """Forward and backward operations per trained token (3x forward),
    causal attention at the mean context (seq+1)/2; recomputation is not
    counted."""
    p = matmul_params(m)
    L = m["num_layers"]
    fwd = (2.0 * (L * p["layer"] + p["head"])
           + L * 4.0 * m["num_heads"] * m["head_dim"] * (seq + 1) / 2.0)
    return 3.0 * fwd

"""How ``correct`` is decided: the timed path against the plain reference.

Serving: a sample of the requests the window finished, drawn from the
seed and always holding the longest one. The reference runs once over
each prompt with its served tokens (one sequence at a time, padded to
the engine's ``max_len`` so one program serves every sample). For each
served token, the gap by which the reference's logit of that token lies
below the reference's best logit at that position; the number compared
is the widest gap. A served token that the reference also ranks first
gaps 0; greedy decoding in bfloat16 picks a near-tie's other member now
and then, by a little.

The serving precision control (``serve_gap_and_control``) is the
reference itself with every linear layer and the head computed on
float8 (e4m3) operands, scaled per row and per column: the step below
the configuration's bfloat16. At each position of the same sequences it
reads the gap of the token the float8 reference ranks first.

Training: see ``train_numbers``. Its precision control is the program's
own lower-precision path, its int8 gradient compression
(``grad_compression: "int8_ef"``) switched on (``readings.py``).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import model as ref

F8_MAX = 448.0


def _f8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm_f8(x, w):
    """A float32 product of float8-rounded operands (rows of ``x`` and
    columns of ``w`` scaled to the e4m3 range)."""
    return jnp.matmul(_f8(x.astype(jnp.float32), -1),
                      _f8(w.astype(jnp.float32), 0), precision=ref.HI)


def serve_sample(finished, seed: int, n: int):
    """``n`` finished requests drawn from the seed, the longest first."""
    from benchmarks.chip.traffic import generator
    if not finished:
        return []
    order = sorted(finished, key=lambda q: -(q.tokens.size + len(q.generated)))
    rest = order[1:]
    pick = generator(seed, 7).permutation(len(rest))[: max(0, n - 1)]
    return [order[0]] + [rest[int(i)] for i in sorted(pick)]


@partial(jax.jit, static_argnames=("m", "rows", "control"))
def _gaps(params, tokens, first, served, valid, *, m, rows, control):
    """Per served row: (reference best - reference at the served token,
    reference best - reference at the control's first choice)."""
    m = dict(m)
    h = ref.hidden(params, m, tokens)
    idx = jnp.clip(first + jnp.arange(rows), 0, tokens.shape[0] - 1)
    lg = ref.logits(params, m, h[idx])
    best = jnp.max(lg, axis=-1)
    g = best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
    g = jnp.where(valid, g, 0.0)
    if not control:
        return g, g
    hc = ref.hidden(params, m, tokens, mm_f8)
    lc = ref.logits(params, m, hc[idx], mm_f8)
    pick = jnp.argmax(lc, axis=-1)
    gc = best - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
    return g, jnp.where(valid, gc, 0.0)


def _serve_gaps(params, m: Dict, seqs, max_len: int, control: bool):
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    rows = max(len(g) for _, g in seqs)
    key = tuple(sorted(m.items()))
    worst = worst_c = 0.0
    for prompt, gen in seqs:
        seq = np.zeros(max_len, np.int32)
        full = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        seq[: full.size] = full
        served = np.zeros(rows, np.int32)
        served[: len(gen)] = gen
        valid = np.arange(rows) < len(gen)
        g, gc = _gaps(params, jnp.asarray(seq), prompt.size - 1,
                      jnp.asarray(served), jnp.asarray(valid), m=key,
                      rows=rows, control=control)
        worst = max(worst, float(jnp.max(g)))
        worst_c = max(worst_c, float(jnp.max(gc)))
    return worst, worst_c


def serve_gap(params, m: Dict, seqs, max_len: int) -> float:
    """Widest reference-logit gap of the served tokens."""
    return _serve_gaps(params, m, seqs, max_len, control=False)[0]


def serve_gap_and_control(params, m: Dict, seqs, max_len: int):
    """(the served tokens' widest gap, the float8 control's)."""
    return _serve_gaps(params, m, seqs, max_len, control=True)


# ---------------------------------------------------------------- train
def leaf_norms_dev(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree_util.tree_leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(leaf_norms_dev)(tree))


def reference_train(params, m: Dict, tc: Dict, batches):
    """Three AdamW steps of the plain reference from ``params`` (f32),
    which it consumes (their buffers are donated to the updates).

    AdamW as published (decoupled decay, scaled by the learning rate),
    after clipping by the global norm, at the configuration's schedule:
    linear warm-up to ``learning_rate`` over ``warmup_steps``, then a
    cosine to a tenth of it at ``total_steps``. Returns (losses, leaf
    norms of the first clipped gradient, leaf norms of the change of the
    parameters after the steps)."""
    b1, b2, eps, wd = tc["b1"], tc["b2"], tc["eps"], tc["weight_decay"]
    m_d = dict(m)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    # the starting point waits on the host: the device holds one set of
    # weights, the two moments and one step's gradient program
    p0 = [np.asarray(a) for a in jax.tree_util.tree_leaves(p)]

    def lr_at(step):
        warm = min(step / max(tc["warmup_steps"], 1), 1.0)
        prog = np.clip((step - tc["warmup_steps"])
                       / max(tc["total_steps"] - tc["warmup_steps"], 1), 0, 1)
        return tc["learning_rate"] * warm * (0.1 + 0.9 * 0.5
                                             * (1 + np.cos(np.pi * prog)))

    @jax.jit
    def grad(p, tokens, labels):
        return jax.value_and_grad(
            lambda p: ref.loss(p, m_d, tokens, labels))(p)

    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, mo, vo, count, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, tc["grad_clip"] / (gn + 1e-9))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        mo = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mo, g)
        vo = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                    vo, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        p = jax.tree_util.tree_map(
            lambda w, a, b: w - lr * (a / c1 / (jnp.sqrt(b / c2) + eps)
                                      + wd * w), p, mo, vo)
        return p, leaf_norms_dev(g), mo, vo

    mo = jax.tree_util.tree_map(jnp.zeros_like, p)
    vo = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, g_first = [], None
    for step, b in enumerate(batches):
        loss, g = grad(p, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        p, gnorms, mo, vo = update(p, g, mo, vo, float(step + 1),
                                   float(lr_at(step)))
        del g
        losses.append(float(loss))
        if g_first is None:
            g_first = np.asarray(gnorms)
    change = np.asarray([np.linalg.norm((np.asarray(a) - a0).ravel())
                         for a, a0 in zip(jax.tree_util.tree_leaves(p), p0)])
    return np.asarray(losses), g_first, change


def _leaf_gaps(prog: np.ndarray, refn: np.ndarray, keep=None) -> np.ndarray:
    """Each leaf's gap of norms over max(its reference norm, the median
    leaf's); leaves not kept read 0."""
    keep = np.ones(refn.shape, bool) if keep is None else keep
    base = np.maximum(refn, np.median(refn[keep]))
    return np.where(keep, np.abs(prog - refn) / base, 0.0)


def _leaf_gap(prog: np.ndarray, refn: np.ndarray, keep=None) -> float:
    return float(np.max(_leaf_gaps(prog, refn, keep)))


def worst_leaves(prog, refr) -> Dict[str, int]:
    """The index of the leaf that sets ``grad_gap`` and ``update_gap``."""
    (_, gp, cp), (_, gr, cr) = prog, refr
    keep = gr >= 1e-3 * np.median(gr)
    return {"grad_gap": int(np.argmax(_leaf_gaps(gp, gr))),
            "update_gap": int(np.argmax(_leaf_gaps(cp, cr, keep)))}


def train_numbers(prog, refr) -> Dict[str, float]:
    """The numbers compared for a training cell.

    ``loss_gap``: the largest relative gap of the first steps' losses.
    ``grad_gap``: over leaves, the gap between the norms of the program's
    and the reference's first clipped gradient (the program's read from
    its first moment after one step: m = (1 - b1) g), over the larger of
    the reference leaf's norm and the median leaf's.
    ``update_gap``: the same for the change of the parameters over the
    steps, leaving out leaves whose reference gradient is under a
    thousandth of the median leaf's (they move by round-off alone)."""
    (lp, gp, cp), (lr_, gr, cr) = prog, refr
    keep = gr >= 1e-3 * np.median(gr)
    return {"loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
            "grad_gap": _leaf_gap(gp, gr),
            "update_gap": _leaf_gap(cp, cr, keep)}

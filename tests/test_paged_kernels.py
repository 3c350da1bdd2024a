"""Parity matrix for the Pallas serving kernels (interpret mode on CPU)
against the pure-jnp reference compositions, on HOSTILE page tables:
out-of-order pages, partially filled last pages, unmapped tail entries,
idle slots. Plus the engine-level bit-consistency and kernel-tier
waste-counter acceptance checks, and the 2-device sharded fast paths in
a subprocess.

The kernels must be drop-in: identical pool contents (bit for bit,
the store epilogue is an exact copy after the pool-dtype round-trip),
identical store-site counters, and attention outputs within float
tolerance of the scatter->gather->masked-attention reference.
"""
import dataclasses
import os
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.flash_prefill import paged_window_attention
from repro.kernels.paged_attention import paged_decode_attention

KEY = jax.random.PRNGKey(0)

# one table exercising everything at once: slot 0 out-of-order pages +
# partially filled last mapped page, slot 1 short history + unmapped
# tail, slot 2 idle (negative sentinel: no store, output don't-care)
HOSTILE_PT = np.array([[5, 1, 6, -1],
                       [2, 7, -1, -1],
                       [-1, -1, -1, -1]], np.int32)
HOSTILE_IDX = np.array([9, 5, -1], np.int32)
# idle sentinel for width-S windows: the engine parks idle slots below
# -S so every window position stays negative (cf. test_sharding.py)
HOSTILE_IDX_W = np.array([9, 5, -8], np.int32)
B, P, PS, M = 3, 8, 4, 4
HQ, HKV, D = 4, 2, 8


def _pools(dtype):
    ks = jax.random.split(KEY, 2)
    pk = jax.random.normal(ks[0], (P, PS, HKV, D), dtype)
    pv = jax.random.normal(ks[1], (P, PS, HKV, D), dtype)
    return pk, pv


def _rows(S, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, HQ, D), dtype)
    kn = jax.random.normal(ks[1], (B, S, HKV, D), dtype)
    vn = jax.random.normal(ks[2], (B, S, HKV, D), dtype)
    return q, kn, vn


def _decode_ref(q, kn, vn, pk, pv, pt, idx):
    cnt = kref.paged_store_counts(pk, pv, kn, vn, pt, idx, tol=0.0)
    ck, cv = kref.paged_update(pk, pv, kn, vn, pt, idx)
    gk, valid = kref.paged_gather(ck, pt)
    gv, _ = kref.paged_gather(cv, pt)
    out = kref.attention_ref(q, gk.astype(q.dtype), gv.astype(q.dtype),
                             causal=True, q_offset=idx, kv_len=idx + 1,
                             kv_valid=valid)
    return out, ck, cv, cnt


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
def test_decode_kernel_hostile_table(pool_dtype):
    pk, pv = _pools(pool_dtype)
    q, kn, vn = _rows(1)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX)
    want, ck_r, cv_r, cnt_r = _decode_ref(q, kn, vn, pk, pv, pt, idx)
    out, lse, cnt = paged_decode_attention(q, kn, vn, pk, pv, pt, idx,
                                           interpret=True)
    live = np.asarray(idx) >= 0
    tol = 2e-2 if pool_dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    # idle slot: no stores attempted, no elements counted
    assert np.asarray(cnt)[~live].sum() == 0
    assert np.isfinite(np.asarray(lse)[live]).all()


def test_decode_kernel_silent_restore_counts():
    """Storing the value already in the pool (after dtype round-trip)
    must count every element as silent — paper Def. 2 at the store site."""
    pk, pv = _pools(jnp.float32)
    q, kn, vn = _rows(1)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX)
    ck, cv = kref.paged_update(pk, pv, kn, vn, pt, idx)
    _, _, cnt = paged_decode_attention(q, kn, vn, ck, cv, pt, idx,
                                       interpret=True)
    c = np.asarray(cnt)
    live = np.asarray(idx) >= 0
    per_tok = 2 * HKV * D
    assert (c[live, 0] == per_tok).all()
    assert (c[live, 1] == per_tok).all()       # every element silent
    assert (c[:, 2] == 0).all()                # all targets mapped


def test_decode_kernel_gqa_and_full_pages():
    # GQA 8:2, history exactly filling whole pages (idx on page boundary)
    pk = jax.random.normal(KEY, (6, PS, 2, 16), jnp.float32)
    pv = jax.random.normal(jax.random.PRNGKey(9), (6, PS, 2, 16),
                           jnp.float32)
    pt = jnp.array([[4, 2, 0], [1, 3, -1]], jnp.int32)
    idx = jnp.array([PS * 2, PS - 1], jnp.int32)   # new row opens page 3 / fills page 1
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (2, 1, 8, 16), jnp.float32)
    kn = jax.random.normal(ks[1], (2, 1, 2, 16), jnp.float32)
    vn = jax.random.normal(ks[2], (2, 1, 2, 16), jnp.float32)
    want, _, _, cnt_r = _decode_ref(q, kn, vn, pk, pv, pt, idx)
    out, _, cnt = paged_decode_attention(q, kn, vn, pk, pv, pt, idx,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S", [1, 3, 5])
def test_window_kernel_store_hostile_table(pool_dtype, S):
    pk, pv = _pools(pool_dtype)
    q, kw, vw = _rows(S, seed=7)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX_W)
    out, lse, cnt, ck, cv = paged_window_attention(
        q, kw, vw, pk, pv, pt, idx, store=True, interpret=True)
    want, ck_r, cv_r, cnt_r = kref.paged_window_ref(
        q, kw, vw, pk, pv, pt, idx, store=True, tol=0.0)
    live = np.asarray(idx) >= 0
    tol = 2e-2 if pool_dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=tol, rtol=tol)
    # pool writes are exact copies: bit-equal, idle slot untouched
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(ck_r))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(cv_r))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))


def test_window_kernel_rows_past_table_end_drop():
    """A window running past the last mapped page (slot 1: idx 5 + 5
    rows crosses into unmapped page 2) must count dropped elements and
    leave those rows unstored — the dead-store lanes the kernel tier
    reports."""
    pk, pv = _pools(jnp.float32)
    q, kw, vw = _rows(5, seed=13)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX_W)
    _, _, cnt, ck, cv = paged_window_attention(
        q, kw, vw, pk, pv, pt, idx, store=True, interpret=True)
    _, ck_r, cv_r, cnt_r = kref.paged_window_ref(
        q, kw, vw, pk, pv, pt, idx, store=True, tol=0.0)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(ck_r))
    c = np.asarray(cnt)
    assert c[1, 2] > 0                  # slot 1 drops the overflow rows
    assert c[2].sum() == 0              # idle slot counts nothing


@pytest.mark.parametrize("S", [1, 4])
def test_window_kernel_defer_leaves_pool_untouched(S):
    pk, pv = _pools(jnp.float32)
    q, kw, vw = _rows(S, seed=5)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX_W)
    out, _, cnt, ck, cv = paged_window_attention(
        q, kw, vw, pk, pv, pt, idx, store=False, interpret=True)
    want, ck_r, cv_r, cnt_r = kref.paged_window_ref(
        q, kw, vw, pk, pv, pt, idx, store=False, tol=0.0)
    live = np.asarray(idx) >= 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(pv))
    assert np.asarray(cnt).sum() == 0   # defer: no machine-level stores
    assert np.asarray(cnt_r).sum() == 0


def test_window_kernel_store_equals_defer_attention():
    """Overwrite and defer are the same attention math (the verify
    forward must not depend on commit policy) — outputs bit-equal."""
    pk, pv = _pools(jnp.float32)
    q, kw, vw = _rows(3, seed=21)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX_W)
    o1, _, _, _, _ = paged_window_attention(q, kw, vw, pk, pv, pt, idx,
                                            store=True, interpret=True)
    o2, _, _, _, _ = paged_window_attention(q, kw, vw, pk, pv, pt, idx,
                                            store=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_ops_dispatch_parity(monkeypatch):
    """ops.paged_decode / ops.paged_window agree between the two
    dispatch targets (counters included) on the hostile table."""
    pk, pv = _pools(jnp.float32)
    q, kn, vn = _rows(1)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX)
    monkeypatch.setattr(kops, "_use_pallas", lambda: False)
    o_r, ck_r, cv_r, c_r = kops.paged_decode(q, kn, vn, pk, pv, pt, idx,
                                             counters=True)
    monkeypatch.setattr(kops, "_use_pallas", lambda: True)
    o_p, ck_p, cv_p, c_p = kops.paged_decode(q, kn, vn, pk, pv, pt, idx,
                                             counters=True)
    live = np.asarray(idx) >= 0
    np.testing.assert_allclose(np.asarray(o_p)[live], np.asarray(o_r)[live],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(ck_p), np.asarray(ck_r))
    np.testing.assert_array_equal(np.asarray(cv_p), np.asarray(cv_r))
    np.testing.assert_array_equal(np.asarray(c_p), np.asarray(c_r))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("plan", [False, True], ids=["no_plan", "plan"])
@pytest.mark.parametrize("call", ["decode", "verify", "prefill", "defer"])
def test_ops_dispatch_routes_sharded_with_plan(monkeypatch, call, plan,
                                               backend):
    """``ops.paged_decode`` and the stored verify window run their
    sharded entry exactly when the active sharder has a plan for the
    pool (forced here, over a one-device "model" mesh); a prefill window
    and a deferred verify never do. On a stacked pool the sharded call
    cuts the layer out, writes it back and counts the stores outside the
    shard_map, so outputs, pools and counters match the unsharded call."""
    from jax.sharding import Mesh
    from repro.kernels import sharded
    from repro.sharding.ctx import Sharder, sharding_ctx
    monkeypatch.setattr(kops, "_use_pallas", lambda: backend == "pallas")
    sk, sv = _stacked_pools()
    S = 1 if call == "decode" else 3
    q, kn, vn = _rows(S, seed=41)
    pt = jnp.asarray(HOSTILE_PT)
    idx = jnp.asarray(HOSTILE_IDX if S == 1 else HOSTILE_IDX_W)
    fn = (kops.paged_decode if call == "decode" else
          partial(kops.paged_window, store=call != "defer",
                  verify=call != "prefill"))
    run = jax.jit(lambda *a: fn(*a, layer=1, counters=True))
    args = (q, kn, vn, sk, sv, pt, idx)
    want = run(*args)

    routed = []
    for name in ("decode_paged_attention_sharded",
                 "verify_paged_attention_sharded"):
        def spy(*a, _real=getattr(sharded, name), _name=name, **kw):
            routed.append(_name)
            return _real(*a, **kw)
        monkeypatch.setattr(sharded, name, spy)
    monkeypatch.setattr(sharded, "paged_shard_plan",
                        lambda *a: ((), ("model",)) if plan else None)
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    with sharding_ctx(Sharder(mesh, {})):
        got = jax.jit(lambda *a: fn(*a, layer=1, counters=True))(*args)
    entry = {"decode": "decode_paged_attention_sharded",
             "verify": "verify_paged_attention_sharded"}.get(call)
    assert routed == ([entry] if plan and entry else [])
    live = np.asarray(idx) >= 0
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live],
                               atol=2e-5, rtol=2e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------
# layer-stacked pools: the decode scan carries (L, P, page, Hkv, D) and
# each layer reads and writes its own slab in place
# ---------------------------------------------------------------------

NL = 3


def _stacked_pools():
    ks = jax.random.split(jax.random.PRNGKey(31), 2)
    sk = jax.random.normal(ks[0], (NL, P, PS, HKV, D), jnp.float32)
    sv = jax.random.normal(ks[1], (NL, P, PS, HKV, D), jnp.float32)
    return sk, sv


def _assert_stacked_equal(layer, sk, sv, got, want):
    """``got`` from a stacked call at ``layer``, ``want`` from the same
    call on that layer's pools: equal outputs, and the stacked pools
    returned equal the inputs with ``want``'s pools at ``layer``."""
    *g_rest, g_k, g_v = got
    *w_rest, w_k, w_v = want
    for g, w in zip(g_rest, w_rest):
        if w is None:
            assert g is None
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(g_k),
                                  np.asarray(sk.at[layer].set(w_k)))
    np.testing.assert_array_equal(np.asarray(g_v),
                                  np.asarray(sv.at[layer].set(w_v)))


@pytest.mark.parametrize("layer", [0, NL - 1])
def test_decode_kernel_stacked_pool_equals_layer_pool(layer):
    sk, sv = _stacked_pools()
    q, kn, vn = _rows(1)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX)
    got = paged_decode_attention(q, kn, vn, sk, sv, pt, idx,
                                 layer=jnp.int32(layer), interpret=True)
    want = paged_decode_attention(q, kn, vn, sk[layer], sv[layer], pt, idx,
                                  interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("store", [True, False])
@pytest.mark.parametrize("layer", [0, NL - 1])
def test_window_kernel_stacked_pool_equals_layer_pool(layer, store):
    sk, sv = _stacked_pools()
    q, kw, vw = _rows(3, seed=7)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX_W)
    got = paged_window_attention(q, kw, vw, sk, sv, pt, idx,
                                 layer=jnp.int32(layer), store=store,
                                 interpret=True)
    want = paged_window_attention(q, kw, vw, sk[layer], sv[layer], pt, idx,
                                  store=store, interpret=True)
    _assert_stacked_equal(layer, sk, sv, got, want)


@pytest.mark.parametrize("counters", [False, True])
@pytest.mark.parametrize("pallas", [False, True], ids=["ref", "pallas"])
@pytest.mark.parametrize("layer", [0, NL - 1])
@pytest.mark.parametrize("call,S,store", [("decode", 1, True),
                                          ("window", 3, True),
                                          ("window", 3, False)],
                         ids=["decode", "window-store", "window-defer"])
def test_ops_stacked_pool_equals_layer_pool(monkeypatch, call, S, store,
                                            layer, pallas, counters):
    """ops.paged_decode / ops.paged_window on the stacked pools at
    ``layer`` equal the same call on that layer's pools, on both
    dispatch targets, with the store-site counters on and off."""
    monkeypatch.setattr(kops, "_use_pallas", lambda: pallas)
    sk, sv = _stacked_pools()
    q, kn, vn = _rows(S, seed=19)
    pt = jnp.asarray(HOSTILE_PT)
    idx = jnp.asarray(HOSTILE_IDX if S == 1 else HOSTILE_IDX_W)
    if call == "decode":
        fn = partial(kops.paged_decode, counters=counters)
    else:
        fn = partial(kops.paged_window, store=store, counters=counters)
    out, ck, cv, cnt = fn(q, kn, vn, sk, sv, pt, idx,
                          layer=jnp.int32(layer))
    w_out, w_k, w_v, w_cnt = fn(q, kn, vn, sk[layer], sv[layer], pt, idx)
    live = np.asarray(idx) >= 0
    np.testing.assert_array_equal(np.asarray(out)[live],
                                  np.asarray(w_out)[live])
    _assert_stacked_equal(layer, sk, sv, (cnt, ck, cv), (w_cnt, w_k, w_v))


def test_ref_stacked_store_counts_and_gather():
    """The ref store counters and gather on a stacked pool read only
    ``layer``'s slab."""
    sk, sv = _stacked_pools()
    _, kn, vn = _rows(1)
    pt, idx = jnp.asarray(HOSTILE_PT), jnp.asarray(HOSTILE_IDX)
    for layer in (0, NL - 1):
        # the pools already hold the row at layer: every store silent
        hk, hv = kref.paged_update(sk, sv, kn, vn, pt, idx,
                                   layer=jnp.int32(layer))
        cnt = kref.paged_store_counts(hk, hv, kn, vn, pt, idx,
                                      layer=jnp.int32(layer))
        want = kref.paged_store_counts(hk[layer], hv[layer], kn, vn, pt,
                                       idx)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(want))
        assert (np.asarray(cnt)[:2, 1] == 2 * HKV * D).all()
        g, valid = kref.paged_gather(hk, pt, jnp.int32(layer))
        g1, valid1 = kref.paged_gather(hk[layer], pt)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g1))
        np.testing.assert_array_equal(np.asarray(valid), np.asarray(valid1))


# ---------------------------------------------------------------------
# model-level: the kcnt leaf rides the decode scan and reports exact
# element counts at every serving site
# ---------------------------------------------------------------------

def _smoke_model():
    from repro.configs import registry
    from repro.models.zoo import build_model
    cfg = dataclasses.replace(registry.get_config("qwen3-1.7b").smoke(),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_model_counter_flow_prefill_decode_verify_commit():
    cfg, model, params = _smoke_model()
    nb, page_size, max_len = 3, 4, 32
    cache = model.init_paged_cache(params, nb, max_len, page_size=page_size,
                                   kv_dtype=jnp.float32,
                                   kernel_counters=True)
    base_pt = jnp.arange(nb * (max_len // page_size),
                         dtype=jnp.int32).reshape(nb, -1)
    cache = model.with_page_table(cache, base_pt)
    per_tok = 2 * cfg.num_kv_heads * cfg.head_dim

    def counts():
        kc = model.kernel_counters(cache)
        assert kc is not None
        return {n: np.asarray(c) for n, c in kc.items()}

    toks = jax.random.randint(jax.random.PRNGKey(1), (nb, 5), 0,
                              cfg.vocab_size)
    lengths = jnp.full((nb,), 5, jnp.int32)
    logits, cache = model.prefill(params, cache, toks, lengths=lengths)
    for n, c in counts().items():
        assert (c[..., 0] == 5 * per_tok).all(), (n, c)
        assert (c[..., 1:] == 0).all(), (n, c)

    tok1 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    _, cache = model.decode_step(params, cache, tok1)
    for n, c in counts().items():
        assert (c[..., 0] == per_tok).all() and (c[..., 1:] == 0).all()

    # silent re-store: rewind the write index, decode the same token
    rewound = model.with_cache_index(cache, lengths)
    _, rewound = model.decode_step(params, rewound, tok1)
    kc = model.kernel_counters(rewound)
    for n, c in kc.items():
        c = np.asarray(c)
        assert (c[..., 0] == per_tok).all() and (c[..., 1] == per_tok).all()

    draft = jax.random.randint(jax.random.PRNGKey(2), (nb, 3), 0,
                               cfg.vocab_size)
    lo, cache_ov = model.verify(params, cache, draft, commit=True)
    kc = model.kernel_counters(cache_ov)
    for n, c in kc.items():
        c = np.asarray(c)
        assert (c[..., 0] == 3 * per_tok).all() and (c[..., 2] == 0).all()

    lo2, cache_df = model.verify(params, cache, draft, commit=False)
    kc = model.kernel_counters(cache_df)
    for n, c in kc.items():
        assert (np.asarray(c) == 0).all()       # defer: nothing stored
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo2))

    start = jnp.full((nb,), 6, jnp.int32)
    accept = jnp.array([2, 0, 3], jnp.int32)
    cache_cm = model.commit_verify(cache_df, start, accept)
    kc = model.kernel_counters(cache_cm)
    for n, c in kc.items():
        c = np.asarray(c)
        assert (c[..., 0] == np.asarray(accept)[None, :] * per_tok).all()
        assert (c[..., 2] == 0).all()


# ---------------------------------------------------------------------
# engine-level: greedy serve bit-consistency and the kernel-tier
# rejected_draft_store acceptance criterion
# ---------------------------------------------------------------------

def _serve(model, params, cfg, *, kv="paged", drafter=None, rollback=True,
           detectors=None, kernel_counters=False):
    from repro.serve.engine import Request, ServeEngine
    eng = ServeEngine(model, params, num_slots=2, max_len=32,
                      kv_layout=kv, page_size=8, drafter=drafter,
                      spec_k=3, spec_rollback=rollback, detectors=detectors,
                      kernel_counters=kernel_counters)
    rng = np.random.RandomState(3)
    for i, (plen, gen, arr) in enumerate([(8, 5, 0), (5, 7, 0), (7, 3, 1)]):
        eng.submit(Request(rid=f"q{i}",
                           tokens=rng.randint(0, cfg.vocab_size,
                                              size=plen).astype(np.int32),
                           max_new_tokens=gen, arrival=arr))
    fin = eng.run(max_steps=400)
    return {rid: fin[rid].generated for rid in fin}, eng


class GarbageDrafter:
    def observe(self, t):
        pass

    def propose(self, h, k):
        return np.full(k, 7, np.int32)


def test_engine_greedy_identical_dense_paged_pallas(monkeypatch):
    cfg, model, params = _smoke_model()
    monkeypatch.setattr(kops, "_use_pallas", lambda: False)
    dense, _ = _serve(model, params, cfg, kv="dense")
    paged, _ = _serve(model, params, cfg, kv="paged")
    assert dense == paged
    monkeypatch.setattr(kops, "_use_pallas", lambda: True)
    pallas, _ = _serve(model, params, cfg, kv="paged")
    assert pallas == dense


def test_engine_kernel_tier_rejected_draft_fraction():
    from repro.configs.base import ProfilerConfig
    from repro.core.detectors import ServingDetectors
    cfg, model, params = _smoke_model()
    base, _ = _serve(model, params, cfg)

    # counters on, no drafter: outputs unchanged, silent-store checked
    det = ServingDetectors(ProfilerConfig(enabled=True))
    out, eng = _serve(model, params, cfg, detectors=det,
                      kernel_counters=True)
    assert out == base
    assert det.kernel.checked.get("kernel_silent_store", 0) > 0
    assert det.kernel.fractions().get("kernel_dead_store", 1.0) == 0.0
    assert 4 in det.combined().tiers

    # overwrite commit: kernel-tier rejected fraction == 1 - accept rate
    det1 = ServingDetectors(ProfilerConfig(enabled=True))
    out1, eng1 = _serve(model, params, cfg, drafter=GarbageDrafter(),
                        rollback=False, detectors=det1,
                        kernel_counters=True)
    assert out1 == base
    acc = eng1.stats["draft_accepted"] / eng1.stats["draft_proposed"]
    fr1 = det1.kernel.fractions()["kernel_rejected_draft_store"]
    assert abs(fr1 - (1.0 - acc)) < 1e-9
    assert fr1 == det1.report.fractions()["rejected_draft_store"]

    # rollback commit: provably zero rejected stores
    det2 = ServingDetectors(ProfilerConfig(enabled=True))
    out2, _ = _serve(model, params, cfg, drafter=GarbageDrafter(),
                     rollback=True, detectors=det2, kernel_counters=True)
    assert out2 == base
    assert det2.kernel.fractions()["kernel_rejected_draft_store"] == 0.0
    assert det2.kernel.checked["kernel_rejected_draft_store"] > 0


# ---------------------------------------------------------------------
# sharded fast paths: 2 virtual devices, Pallas vs ref, in a subprocess
# so the main process keeps its 1-device view
# ---------------------------------------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.kernels import ops
from repro.kernels import sharded as sh

mesh = Mesh(np.array(jax.devices()).reshape(2), ("model",))
B, Hq, Hkv, D = 3, 4, 2, 8
P, ps, M = 8, 4, 4
ks = jax.random.split(jax.random.PRNGKey(0), 8)
pt = jnp.array([[5, 1, 6, -1], [2, 7, -1, -1], [-1, -1, -1, -1]], jnp.int32)
idx = jnp.array([9, 5, -1], jnp.int32)

for dtype in (jnp.float32, jnp.bfloat16):
    pool_k = jax.random.normal(ks[0], (P, ps, Hkv, D), dtype)
    pool_v = jax.random.normal(ks[1], (P, ps, Hkv, D), dtype)
    q = jax.random.normal(ks[2], (B, 1, Hq, D), jnp.float32)
    kn = jax.random.normal(ks[3], (B, 1, Hkv, D), jnp.float32)
    vn = jax.random.normal(ks[4], (B, 1, Hkv, D), jnp.float32)
    qw = jax.random.normal(ks[5], (B, 3, Hq, D), jnp.float32)
    kw = jax.random.normal(ks[6], (B, 3, Hkv, D), jnp.float32)
    vw = jax.random.normal(ks[7], (B, 3, Hkv, D), jnp.float32)
    for entry, a in ((sh.decode_paged_attention_sharded, (q, kn, vn)),
                     (sh.verify_paged_attention_sharded, (qw, kw, vw))):
        with mesh:
            ops._use_pallas = lambda: False
            o_r, ck_r, cv_r = entry(*a, pool_k, pool_v, pt, idx, mesh=mesh,
                                    batch_axes=(), seq_axes=("model",))
            ops._use_pallas = lambda: True
            o_p, ck_p, cv_p = entry(*a, pool_k, pool_v, pt, idx, mesh=mesh,
                                    batch_axes=(), seq_axes=("model",))
        np.testing.assert_array_equal(np.asarray(ck_r), np.asarray(ck_p))
        np.testing.assert_array_equal(np.asarray(cv_r), np.asarray(cv_p))
        np.testing.assert_allclose(np.asarray(o_r[:2], np.float32),
                                   np.asarray(o_p[:2], np.float32),
                                   rtol=2e-5, atol=2e-5)

# model level: the decode scan's stacked pool through the sharded paths
# (each layer is cut out for the shard_map and written back) equals the
# unsharded stacked-pool path
import dataclasses
from repro.configs import registry
from repro.models.zoo import build_model
from repro.sharding.ctx import Sharder, sharding_ctx

ops._use_pallas = lambda: False
cfg = dataclasses.replace(registry.get_config("qwen3-1.7b").smoke(),
                          dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh2 = Mesh(np.array(jax.devices()).reshape(1, 2), ("data", "model"))
sharder = Sharder(mesh2, {}, batch_axes=("data",))
cache = model.init_paged_cache(params, 2, 64, page_size=4, num_pages=256,
                               kv_dtype=jnp.float32)
cache = model.with_page_table(cache, jnp.array(
    [[3, 200, 7, 130] + [-1] * 12, [250, 1, -1, -1] + [-1] * 12], jnp.int32))
cache = model.with_cache_index(cache, jnp.array([9, 5], jnp.int32))
for width, spec in ((1, None), (3, "overwrite")):
    toks = jax.random.randint(ks[0], (2, width), 0, cfg.vocab_size)
    step = jax.jit(lambda c, t: model.decode_step(params, c, t, spec=spec))
    want, w_cache = step(cache, toks)
    with mesh2, sharding_ctx(sharder):
        step = jax.jit(lambda c, t: model.decode_step(params, c, t,
                                                      spec=spec))
        got, g_cache = step(cache, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # later layers' rows carry the flash combine's round-off
    for a, b in zip(jax.tree_util.tree_leaves(g_cache),
                    jax.tree_util.tree_leaves(w_cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
print("SUBPROC_OK")
"""


def test_sharded_pallas_matches_ref_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=420)
    assert "SUBPROC_OK" in out.stdout, out.stderr[-3000:]

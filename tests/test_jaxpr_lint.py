"""Tier-0 static jaxpr lint: golden corpus.

Each waste rule gets a planted-positive program AND a clean twin that
differs only in the property the rule checks — the twin must produce
ZERO findings of that kind (false-positive guard). Positives assert the
kind, the byte accounting, and the ⟨C1⟩ provenance file:line pointing
back into THIS file.
"""
import os

import jax
import jax.numpy as jnp

from repro.core.findings import TIER_STATIC, WasteProfile, merge
from repro.core.jaxpr_lint import lint_fn, lint_jaxpr

HERE = os.path.basename(__file__)


def kinds(prof):
    return sorted({f.kind for f in prof.findings})


def only(prof, kind):
    fs = [f for f in prof.findings if f.kind == kind]
    assert fs, f"no {kind} finding; got {kinds(prof)}"
    return fs


def assert_here(finding, lo=0, hi=10 ** 9):
    """Provenance points into this test file at a plausible line."""
    f = finding.meta.get("file", "")
    assert os.path.basename(f) == HERE, f"provenance file {f!r}"
    assert lo <= finding.meta.get("line", 0) <= hi


# --------------------------------------------------------------- dead store
def test_dead_store_overwritten_region():
    def f(x, u1, u2):
        y = jax.lax.dynamic_update_slice(x, u1, (3,))       # dead: fully
        return jax.lax.dynamic_update_slice(y, u2, (3,))    # overwritten

    x, u = jnp.zeros(17), jnp.ones(5)
    prof = lint_fn(f, x, u, u, subject="t")
    ds = only(prof, "dead_store")
    assert len(ds) == 1
    assert ds[0].bytes == 5 * 4                      # the dead update
    assert ds[0].tier == TIER_STATIC
    assert_here(ds[0])
    assert ds[0].c2, "C2 must name the overwriting store"


def test_dead_store_clean_twin_distinct_offsets():
    def f(x, u1, u2):
        y = jax.lax.dynamic_update_slice(x, u1, (0,))
        return jax.lax.dynamic_update_slice(y, u2, (9,))

    prof = lint_fn(f, jnp.zeros(17), jnp.ones(5), jnp.ones(5), subject="t")
    assert not [f for f in prof.findings if f.kind == "dead_store"]
    assert prof.checked.get("dead_store", 0) == 2    # both sites checked


def test_dead_store_result_never_read():
    def f(x, u):
        _ = jax.lax.dynamic_update_slice(x, u, (3,))
        return x.sum()

    prof = lint_fn(f, jnp.zeros(17), jnp.ones(5), subject="t")
    ds = only(prof, "dead_store")
    assert "never read" in ds[0].meta["rule"]
    assert_here(ds[0])


# ------------------------------------------------------------- silent store
def test_silent_store_zero_add_identity():
    def f(x):
        return x + 0.0                                # provably x

    prof = lint_fn(f, jnp.zeros((3, 5)), subject="t")
    ss = only(prof, "silent_store")
    assert ss[0].bytes == 3 * 5 * 4
    assert_here(ss[0])


def test_silent_store_clean_twin_nonidentity():
    def f(x):
        return x + 1.0

    prof = lint_fn(f, jnp.zeros((3, 5)), subject="t")
    assert not [f for f in prof.findings if f.kind == "silent_store"]


def test_silent_store_slice_written_back_same_offsets():
    def f(x):
        s = jax.lax.dynamic_slice(x, (3,), (5,))
        return jax.lax.dynamic_update_slice(x, s, (3,))   # resident value

    prof = lint_fn(f, jnp.ones(17), subject="t")
    ss = only(prof, "silent_store")
    assert "resident" in ss[0].meta["rule"]
    assert_here(ss[0])


def test_silent_store_clean_twin_modified_before_writeback():
    def f(x):
        s = jax.lax.dynamic_slice(x, (3,), (5,))
        return jax.lax.dynamic_update_slice(x, s * 2.0, (3,))

    prof = lint_fn(f, jnp.ones(17), subject="t")
    assert not [f for f in prof.findings if f.kind == "silent_store"]


def test_silent_store_clean_twin_different_offsets():
    def f(x):
        s = jax.lax.dynamic_slice(x, (0,), (5,))
        return jax.lax.dynamic_update_slice(x, s, (9,))   # moved, not silent

    prof = lint_fn(f, jnp.ones(17), subject="t")
    assert not [f for f in prof.findings if f.kind == "silent_store"]


def test_silent_store_scatter_writeback():
    def f(x, i):
        return x.at[i].set(x[i])                      # gather -> scatter back

    def g(x, i):
        return x.at[i].set(x[i] + 1.0)

    i = jnp.array([2, 11])
    assert "silent_store" in kinds(lint_fn(f, jnp.ones(17), i, subject="t"))
    assert "silent_store" not in kinds(lint_fn(g, jnp.ones(17), i,
                                               subject="t"))


# ----------------------------------------------------------- redundant load
def test_redundant_load_loop_invariant_gather_in_scan():
    def f(table, idx, xs):
        def body(c, x):
            row = jnp.take(table, idx, axis=0)        # invariant per trip
            return c + row.sum() + x, None
        out, _ = jax.lax.scan(body, 0.0, xs)
        return out

    table = jnp.ones((13, 7))
    prof = lint_fn(f, table, jnp.array([1, 4]), jnp.arange(6.0), subject="t")
    rl = only(prof, "redundant_load")
    # re-executed length-1 = 5 extra trips of a (2,7) f32 gather
    assert rl[0].bytes == 5 * 2 * 7 * 4
    assert "scan[length=6]" in rl[0].meta["rule"]


def test_redundant_load_clean_twin_varying_index():
    def f(table, xs):
        def body(c, x):
            row = jnp.take(table, x.astype(jnp.int32), axis=0)
            return c + row.sum(), None
        out, _ = jax.lax.scan(body, 0.0, xs)
        return out

    prof = lint_fn(f, jnp.ones((13, 7)), jnp.arange(6.0), subject="t")
    assert not [f for f in prof.findings if f.kind == "redundant_load"]


def test_redundant_load_duplicate_gather_same_scope():
    def f(x):
        a = jax.lax.dynamic_slice(x, (2,), (5,))
        b = jax.lax.dynamic_slice(x, (2,), (5,))      # identical load
        return a + b

    prof = lint_fn(f, jnp.ones(17), subject="t")
    rl = only(prof, "redundant_load")
    assert rl[0].bytes == 5 * 4                       # one extra copy
    assert_here(rl[0])


def test_redundant_load_clean_twin_distinct_slices():
    def f(x):
        a = jax.lax.dynamic_slice(x, (0,), (5,))
        b = jax.lax.dynamic_slice(x, (9,), (5,))
        return a + b

    prof = lint_fn(f, jnp.ones(17), subject="t")
    assert not [f for f in prof.findings if f.kind == "redundant_load"]


# -------------------------------------------------------------- dead params
def test_dead_param_moe_expert_never_dispatched():
    """The MoE paydirt: routing ignores expert 1, its weights are dead."""
    def f(params, x):
        # "router" statically picks expert 0 only
        h = x @ params["experts"]["e0"]["w"]
        return h.sum() + params["bias"].sum()

    params = {"experts": {"e0": {"w": jnp.ones((7, 7))},
                          "e1": {"w": jnp.ones((7, 7))}},   # dead
              "bias": jnp.zeros(7)}
    prof = lint_fn(f, params, jnp.ones((3, 7)), subject="moe")
    dp = only(prof, "dead_param")
    assert len(dp) == 1
    assert dp[0].bytes == 7 * 7 * 4
    assert "e1" in dp[0].meta["path"]                 # names the buffer
    assert dp[0].meta["subject"] == "moe"


def test_dead_param_clean_twin_all_used():
    def f(params, x):
        h = x @ params["experts"]["e0"]["w"] + x @ params["experts"]["e1"]["w"]
        return h.sum() + params["bias"].sum()

    params = {"experts": {"e0": {"w": jnp.ones((7, 7))},
                          "e1": {"w": jnp.ones((7, 7))}},
              "bias": jnp.zeros(7)}
    prof = lint_fn(f, params, jnp.ones((3, 7)), subject="moe")
    assert not [f for f in prof.findings if f.kind == "dead_param"]
    assert prof.checked.get("dead_param", 0) == 4     # every invar checked


# ----------------------------------------------------------- infrastructure
def test_lint_runs_abstract_no_allocation():
    def f(x, u1, u2):
        y = jax.lax.dynamic_update_slice(x, u1, (3,))
        return jax.lax.dynamic_update_slice(y, u2, (3,))

    sds = jax.ShapeDtypeStruct
    prof = lint_fn(f, sds((17,), jnp.float32), sds((5,), jnp.float32),
                   sds((5,), jnp.float32), subject="abstract")
    assert "dead_store" in kinds(prof)


def test_lint_jaxpr_entry_point_and_tier():
    closed = jax.make_jaxpr(lambda x: x + 0.0)(jnp.ones(4))
    prof = lint_jaxpr(closed, subject="direct")
    assert prof.tiers == [TIER_STATIC]
    assert all(f.tier == TIER_STATIC for f in prof.findings)


def test_tier0_merges_with_other_tiers():
    p0 = lint_fn(lambda x: x + 0.0, jnp.ones(4), subject="t")
    p3 = WasteProfile(tier=3)
    p3.add_pair("silent_store", 3, ("leaf:a",), ("step",), 64.0)
    merged = merge(p0, p3)
    assert merged.tiers == [TIER_STATIC, 3]
    ss = [f for f in merged.findings if f.kind == "silent_store"]
    assert len(ss) == 2                               # distinct keys coexist
    rt = WasteProfile.from_json(merged.to_json())
    assert rt == merged


def test_identity_chain_through_convert_and_broadcast():
    """0 surviving broadcast_in_dim/convert still proves the identity."""
    def f(x):
        z = jnp.zeros((3, 5), jnp.float32)            # broadcast of literal
        return x + z

    prof = lint_fn(f, jnp.ones((3, 5)), subject="t")
    assert "silent_store" in kinds(prof)


def test_checked_counters_populate_fractions():
    def f(x, u):
        y = jax.lax.dynamic_update_slice(x, u, (3,))
        return jax.lax.dynamic_update_slice(y, u, (3,))

    prof = lint_fn(f, jnp.zeros(17), jnp.ones(5), subject="t")
    fr = prof.fractions()
    assert fr["dead_store"] == 0.5                    # 1 of 2 store sites


# --------------------------------------------------------------- provenance
def test_user_frames_are_never_empty_for_traced_eqns():
    """Every eqn traced from user code carries its user frames: the C1
    context of a tier-0 finding starts at this file, not at the bare
    primitive name."""
    from repro.core.context import context_of_eqn, user_frames

    def f(x):
        return jnp.sin(x) * 2.0

    jaxpr = jax.make_jaxpr(f)(jnp.ones(3)).jaxpr
    assert jaxpr.eqns
    for eqn in jaxpr.eqns:
        frames = user_frames(eqn)
        assert frames, f"no user frames for {eqn.primitive.name}"
        assert os.path.basename(frames[0].file_name) == HERE
        ctx = context_of_eqn(eqn)
        assert len(ctx) > 1 and ctx[-1] == eqn.primitive.name

"""Serving from weights cast to the compute dtype once, at engine build
(`LM.compute_params`): which leaves are cast, that no engine program
casts a weight again, that a cast tree passes through untouched and is
shared between engines, and that greedy outputs stay bit-identical to
the uncast token-by-token loop over float32 weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.configs import registry
from repro.models.zoo import build_model
from repro.serve.decode import (make_engine_prefill, make_engine_tick,
                                make_engine_verify)
from repro.serve.engine import Request, ServeEngine
from repro.serve.spec import ReplayDrafter

KEY = jax.random.PRNGKey(0)
CAST = ("w", "w_up", "w_gate", "w_down", "embed", "head")
KEEP = ("scale", "router")


def _cfg(family):
    if family == "dense":
        return registry.get_config("qwen3-1.7b").smoke()
    cfg = registry.get_config("granite-moe-3b-a800m").smoke()
    # the shared expert is a leaf group of its own: cover it too
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, shared_expert=True))


@pytest.fixture(scope="module", params=["dense", "moe"])
def env(request):
    cfg = _cfg(request.param)
    assert (cfg.dtype, cfg.param_dtype) == ("bfloat16", "float32")
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _name(path):
    return getattr(path[-1], "key", None)


def test_compute_params_casts_matrices_and_keeps_f32_reads(env):
    cfg, model, params = env
    out = model.compute_params(params)
    seen = set()
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(out)):
        name = _name(path)
        seen.add(name)
        assert name in CAST + KEEP, jax.tree_util.keystr(path)
        want = jnp.dtype(cfg.dtype) if name in CAST else jnp.float32
        assert b.dtype == want, jax.tree_util.keystr(path)
        assert b.shape == a.shape
        if name in CAST:
            np.testing.assert_array_equal(
                np.asarray(b), np.asarray(a.astype(cfg.dtype)))
        else:
            assert b is a
    assert {"w", "embed", "scale"} <= seen
    if cfg.family == "moe":
        assert {"w_up", "w_gate", "w_down", "router", "head"} <= seen


def test_cast_tree_passes_through_and_engines_share_it(env):
    cfg, model, params = env
    cast = model.compute_params(params)
    again = model.compute_params(cast)
    leaves = jax.tree_util.tree_leaves(cast)
    assert all(a is b for a, b in zip(leaves,
                                      jax.tree_util.tree_leaves(again)))

    from_raw = ServeEngine(model, params, num_slots=2, max_len=32,
                           kv_layout="paged", page_size=8)
    f32 = sum(a.nbytes for (p, a) in
              jax.tree_util.tree_flatten_with_path(params)[0]
              if _name(p) in KEEP)
    assert from_raw.stats["weights_cast_bytes"] == sum(
        a.nbytes for (p, a) in jax.tree_util.tree_flatten_with_path(params)[0]
        if _name(p) in CAST)
    assert from_raw.stats["weights_f32_bytes"] == f32

    engines = [ServeEngine(model, cast, num_slots=2, max_len=32,
                           kv_layout="paged", page_size=8) for _ in range(2)]
    for eng in engines:
        assert eng.stats["weights_cast_bytes"] == 0
        assert eng.stats["weights_f32_bytes"] == f32
        assert all(a is b for a, b in zip(
            leaves, jax.tree_util.tree_leaves(eng.params)))


# ----------------------------------------------------------------------
# No engine program casts a weight: the jaxprs of tick, prefill, verify
# ----------------------------------------------------------------------
# ops that hand a weight on unchanged but for its view
_VIEW = {"reshape", "squeeze", "slice", "dynamic_slice", "transpose",
         "copy", "copy_p", "expand_dims"}


def _subjaxprs(params):
    for val in params.values():
        for v in val if isinstance(val, (tuple, list)) else (val,):
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def _weight_converts(closed, n_params: int):
    """``convert_element_type`` eqns whose operand is (a view or a
    per-layer slice of) one of the first ``n_params`` inputs of rank >= 2,
    followed through nested jaxprs (jit, scan, remat, custom calls)."""
    found = []

    def walk(jaxpr, marked):
        for eqn in jaxpr.eqns:
            ins = [not isinstance(v, Literal) and v in marked
                   for v in eqn.invars]
            if (eqn.primitive.name == "convert_element_type" and ins[0]
                    and eqn.invars[0].aval.ndim >= 2):
                found.append(eqn.invars[0].aval)
            if eqn.primitive.name in _VIEW and ins and ins[0]:
                marked |= set(eqn.outvars)
            for sub in _subjaxprs(eqn.params):
                if len(sub.invars) == len(eqn.invars):
                    walk(sub, {v for v, m in zip(sub.invars, ins) if m})

    walk(closed.jaxpr, set(closed.jaxpr.invars[:n_params]))
    return found


def _programs(model, eng):
    B, W = eng.num_slots, 4
    i32 = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731
    on = jnp.ones((B,), bool)
    return {
        "tick": (make_engine_tick(model, paged=True),
                 (eng.cache, eng.tokens, on)),
        "prefill": (make_engine_prefill(model, paged=True),
                    (eng.cache, i32(B, 8), on, i32(B), jnp.full((B,), 8,
                                                               jnp.int32),
                     eng.tokens)),
        "verify": (make_engine_verify(model, paged=True, rollback=True),
                   (eng.cache, i32(B, W), on, jnp.full((B,), W - 1,
                                                      jnp.int32))),
    }


@pytest.mark.parametrize("program", ["tick", "prefill", "verify"])
def test_engine_programs_cast_no_weight(env, program):
    cfg, model, params = env
    eng = ServeEngine(model, params, num_slots=2, max_len=32,
                      kv_layout="paged", page_size=8)
    fn, rest = _programs(model, eng)[program]
    n = len(jax.tree_util.tree_leaves(params))
    # the uncast tree does cast its matrices in the program: the walk
    # finds them, so an empty result below is not a blind spot
    assert _weight_converts(jax.make_jaxpr(fn)(params, *rest), n)
    assert _weight_converts(jax.make_jaxpr(fn)(eng.params, *rest), n) == []


# ----------------------------------------------------------------------
# Bit identity: engine over f32 weights == the uncast token loop
# ----------------------------------------------------------------------
MAX_LEN = 24


@pytest.fixture(scope="module")
def served(env):
    """Requests and their greedy continuations from a per-sequence loop
    through ``decode_step`` on the uncast float32 weights, token by
    token."""
    cfg, model, params = env
    step = jax.jit(model.decode_step)
    rng = np.random.RandomState(5)
    # prompts of at most 8 tokens: a routed group never overflows an
    # expert's capacity, so MoE outputs do not depend on the batch
    reqs = [(f"q{i}", rng.randint(0, cfg.vocab_size, plen).astype(np.int32),
             gen, arr)
            for i, (plen, gen, arr) in enumerate([(8, 7, 0), (5, 6, 0),
                                                  (7, 5, 1), (6, 6, 3)])]
    ref = {}
    for rid, prompt, gen, _ in reqs:
        cache = model.init_cache(params, 1, MAX_LEN, kv_dtype=jnp.float32)
        for t in range(prompt.size):
            lg, cache = step(params, cache, jnp.asarray(prompt[None, t:t + 1]))
        out = [int(jnp.argmax(lg[:, -1]))]
        for _ in range(gen - 1):
            lg, cache = step(params, cache,
                             jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(jnp.argmax(lg[:, -1])))
        ref[rid] = out
    return reqs, ref


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "rollback"])
def test_engine_from_f32_weights_matches_uncast_loop(env, served, spec):
    cfg, model, params = env
    reqs, ref = served
    drafter = None
    if spec:
        # drafts the reference continuation with its 4th token wrong:
        # three accepted drafts, then a rejection the pool never sees
        seqs = []
        for rid, toks, _, _ in reqs:
            cont = list(ref[rid])
            cont[3] = (cont[3] + 1) % cfg.vocab_size
            seqs.append(np.concatenate([toks, cont]))
        drafter = ReplayDrafter(seqs)
    eng = ServeEngine(model, params, num_slots=2, max_len=MAX_LEN,
                      kv_layout="paged", page_size=8, drafter=drafter,
                      spec_k=3, spec_rollback=True)
    assert eng.stats["weights_cast_bytes"] > 0
    for rid, toks, gen, arr in reqs:
        eng.submit(Request(rid=rid, tokens=toks.copy(), max_new_tokens=gen,
                           arrival=arr))
    fin = eng.run(max_steps=200)
    assert {rid: list(r.generated) for rid, r in fin.items()} == ref
    if spec:
        assert 0 < eng.stats["draft_accepted"] < eng.stats["draft_proposed"]

"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at qwen3-1.7b widths (Hq=16,
Hkv=8, D=128, page 16) against a *described* v5e chip and compiles it
with the TPU compiler, which refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, too much VMEM, unpartitionable calls).
Others compile the serving engine's whole decode tick and prefill, to
check what the compiler does with the weights it is handed and with the
KV pool it is donated.
The topology is described inside a fixture, so only the worker that
runs this file loads the TPU compiler; where it cannot be described the
tests skip from there.
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_prefill import paged_window_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.models.zoo import build_model
from repro.serve.decode import StepCache, make_engine_tick

B, HQ, HKV, D, PS, M = 4, 16, 8, 128, 16, 36
P = B * M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pools(sds):
    return (sds((P, PS, HKV, D), jnp.float32),
            sds((P, PS, HKV, D), jnp.float32),
            sds((B, M), jnp.int32), sds((B,), jnp.int32))


def test_paged_decode_compiles(sds):
    pk, pv, pt, idx = _pools(sds)
    _compile(paged_decode_attention, sds((B, 1, HQ, D), jnp.bfloat16),
             sds((B, 1, HKV, D), jnp.bfloat16),
             sds((B, 1, HKV, D), jnp.bfloat16), pk, pv, pt, idx)


@pytest.mark.parametrize("S,store", [(128, True), (128, False),
                                     (5, True), (5, False)])
def test_paged_window_compiles(sds, S, store):
    """Prefill width (128) and verify width (k+1 = 5), store and defer."""
    pk, pv, pt, idx = _pools(sds)
    _compile(partial(paged_window_attention, store=store),
             sds((B, S, HQ, D), jnp.bfloat16),
             sds((B, S, HKV, D), jnp.bfloat16),
             sds((B, S, HKV, D), jnp.bfloat16), pk, pv, pt, idx)


@pytest.mark.parametrize("grad", [False, True])
def test_flash_attention_compiles(sds, grad):
    q = sds((2, 1024, HQ, D), jnp.bfloat16)
    kv = sds((2, 1024, HKV, D), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd, q, kv, kv)


@pytest.mark.parametrize("cast", [False, True],
                         ids=["param_dtype", "compute_params"])
def test_engine_tick_converts_no_weight(sds, monkeypatch, cast):
    """A 2-layer engine tick at qwen3-1.7b widths (8 slots, paged f32
    pool, the Pallas decode kernel). Handed float32 weights, the
    optimised HLO converts whole weight stacks on every tick; handed
    `LM.compute_params`' tree, no convert yields a weight's shape."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)
    cfg = dataclasses.replace(registry.get_config("qwen3-1.7b"),
                              num_layers=2)
    model = build_model(cfg)
    params = model.abstract_params()
    if cast:
        params = jax.eval_shape(model.compute_params, params)
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        None, 8, 1024, page_size=PS, num_pages=64, kv_dtype=jnp.float32))
    place = partial(jax.tree_util.tree_map, lambda a: sds(a.shape, a.dtype))
    text = _compile(make_engine_tick(model, paged=True), place(params),
                    place(cache), sds((8, 1), jnp.int32),
                    sds((8,), jnp.bool_))
    weights = {tuple(a.shape) for a in jax.tree_util.tree_leaves(params)
               if a.ndim >= 2}
    assert (2, 2048, 6144) in weights and (2, 6144, 2048) in weights
    converted = {tuple(int(d) for d in m.split(",") if d) for m in
                 re.findall(r"= \w+\[([\d,]*)\]\S*\s+convert\(", text)}
    if cast:
        assert not converted & weights, sorted(converted & weights)
    else:
        assert {(2, 2048, 6144), (2, 6144, 2048)} <= converted


# ---------------------------------------------------------------------
# the KV pool through the engine's donating programs
# ---------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]"
                    r"(\{[^}]*\})? ([\w-]+)\((.*)$")
# memory-space assignment's moves between HBM and VMEM: a pool small
# enough to fit VMEM may be staged there around its update
_STAGING = ("copy-start", "copy-done", "slice-start", "slice-done")


def _computations(text):
    """{name: [instruction lines]} of an optimised HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _pool_values(text, shapes):
    """(name, opcode, detail) of each instruction outside a fusion's body
    that yields an array of one of ``shapes``; a fusion's detail is the
    opcode of its body's root."""
    comps = _computations(text)
    fused = set(re.findall(r"calls=%([\w.-]+)", text))
    root = {n: next(re.search(r"[\]})] ([a-z][\w-]*)\(", ln).group(1)
                    for ln in body if ln.lstrip().startswith("ROOT "))
            for n, body in comps.items() if n in fused}
    out = []
    for name, body in comps.items():
        if name in fused:
            continue
        for line in body:
            m = _INSTR.match(line)
            if not m:
                continue
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            if dims not in shapes:
                continue
            op, rest = m.group(5), m.group(6)
            if op == "fusion":
                detail = root[re.search(r"calls=%([\w.-]+)", rest).group(1)]
            elif op == "custom-call":
                detail = re.search(r'custom_call_target="([^"]+)"',
                                   rest).group(1)
            else:
                detail = ""
            out.append((m.group(1), op, detail))
    return out


def _aliased_params(text):
    return {int(p) for p in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)",
        text.split("\n", 1)[0])}


def _entry_params(text, dims):
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    shape = ",".join(map(str, dims))
    return {int(p) for p in re.findall(
        r"= f32\[" + shape + r"\]\S* parameter\((\d+)\)", entry)}


@pytest.mark.parametrize("program", ["tick", "prefill"])
@pytest.mark.parametrize("layers,pages,fits_vmem",
                         [(2, 64, True), (28, 512, False)],
                         ids=["2x64", "28x512"])
def test_engine_programs_update_pool_in_place(sds, monkeypatch, program,
                                              layers, pages, fits_vmem):
    """The engine's own donating tick and 64-row prefill (qwen3-1.7b
    widths, 8 slots, paged f32 pool), at 2 layers over 64 pages and at
    the deployment's 28 layers over 512. The pool leaves are aliased
    input to output, and no op outside the in-place scatter yields a
    value shaped like the whole pool or one layer's pool: no copy, no
    per-layer slice, no write-back of a slice. The 2-layer pool (8 MB)
    fits VMEM, where the compiler may stage it around the scatter; the
    deployment's (1.88 GB) cannot be, and there nothing is excused."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)
    cfg = dataclasses.replace(registry.get_config("qwen3-1.7b"),
                              num_layers=layers)
    model = build_model(cfg)
    params = jax.eval_shape(model.compute_params, model.abstract_params())
    cache = jax.eval_shape(lambda: model.with_cache_index(
        model.init_paged_cache(None, 8, 1024, page_size=PS,
                               num_pages=pages, kv_dtype=jnp.float32),
        jnp.zeros((8,), jnp.int32)))
    place = partial(jax.tree_util.tree_map, lambda a: sds(a.shape, a.dtype))
    i32 = lambda *s: sds(s, jnp.int32)   # noqa: E731
    on = sds((8,), jnp.bool_)
    rest = {"tick": (i32(8, 1), on),
            "prefill": (i32(8, 64), on, i32(8), i32(8), i32(8, 1))}[program]
    fn = StepCache(model).get(program, paged=True)
    text = fn.lower(place(params), place(cache), *rest).compile().as_text()
    assert "tpu_custom_call" in text

    pool = (layers, pages, PS, HKV, D)
    rows = (layers * pages * PS, HKV, D)
    shapes = {pool, pool[1:], (1,) + pool[1:], rows,
              (pages * PS, HKV, D)}
    pool_params = _entry_params(text, pool)
    assert len(pool_params) == 2                      # K and V
    assert pool_params <= _aliased_params(text), text.split("\n", 1)[0]
    values = _pool_values(text, shapes)
    assert [v for v in values if v[1] == "fusion"
            and v[2] == "scatter"], "no in-place scatter of the pool"
    bad = [v for v in values
           if v[1] not in ("parameter", "get-tuple-element", "bitcast",
                           "while")
           and not (v[1] == "fusion" and v[2] == "scatter")
           and not (fits_vmem and (v[1] in _STAGING
                                   or v[2] == "ConcatBitcast"))]
    assert not bad, bad

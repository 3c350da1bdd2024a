"""Sharding rules: spec trees match param trees for every arch; leaf specs
never imply padding (hypothesis over random leaf shapes); distributed
pieces (fused xent, flash decoding, dry-run lowering) run in a subprocess
with 8 virtual devices so the main test process keeps a 1-device view."""
import subprocess
import sys
import os

import jax
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.configs import registry
from repro.models.zoo import build_model
from repro.sharding.rules import leaf_spec_fsdp, leaf_spec_tp


class FakeMesh:
    def __init__(self, data=16, model=16):
        self.shape = {"data": data, "model": model}


@given(st.lists(st.integers(1, 4096), min_size=1, max_size=4),
       st.sampled_from(["ffn", "embed", "vocab", "experts", None]))
@settings(max_examples=120, deadline=None)
def test_leaf_specs_never_pad(shape, ax):
    """Every sharded dim must be divisible by its mesh axes (no implicit
    GSPMD padding -> honest cost_analysis)."""
    mesh = FakeMesh()
    axes = tuple([ax] + [None] * (len(shape) - 1))
    for fn in (leaf_spec_tp, leaf_spec_fsdp):
        spec = fn(axes, tuple(shape), mesh)
        for dim, names in zip(shape, tuple(spec)):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            n = 1
            for a in names:
                n *= mesh.shape[a]
            assert dim % n == 0


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_spec_trees_match_param_trees(arch):
    """param_specs/opt_specs trees are congruent with the real param tree
    for the FULL config (structure only, no allocation)."""
    cfg = registry.get_config(arch)
    model = build_model(cfg)
    mesh = FakeMesh()
    from repro.sharding import rules

    class S(rules.DpTp):
        def __init__(self):
            self.mesh = mesh
            self.dp = ("data",)
    strat = S()
    abstract = model.abstract_params()
    specs = strat.param_specs(model)
    t1 = jax.tree_util.tree_structure(abstract)
    t2 = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda s: 0, specs,
                               is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    assert t1 == t2


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as PS

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# --- fused vocab-parallel xent == reference (value + grads) -----------
from repro.train.fused_xent import make_fused_xent
B, S, d, V = 4, 8, 16, 32
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (B, S, d), jnp.float32)
w = jax.random.normal(jax.random.PRNGKey(1), (V, d), jnp.float32)
labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
def ref(x, w):
    logits = jnp.einsum('bsd,vd->bsv', x, w)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)
with mesh:
    fused = make_fused_xent(mesh, ("data",), 0.0)
    lf = jax.jit(fused)(x, w, labels)
    assert abs(float(lf) - float(ref(x, w))) < 1e-5
    gx, gw = jax.jit(jax.grad(fused, argnums=(0, 1)))(x, w, labels)
    rx, rw = jax.grad(lambda x, w: ref(x, w), argnums=(0, 1))(x, w)
    assert float(jnp.abs(gx - rx).max()) < 1e-5
    assert float(jnp.abs(gw - rw).max()) < 1e-5

# --- flash decoding == masked reference -------------------------------
from repro.serve.flash_decode import decode_attention_sharded
from repro.kernels.ref import attention_ref
B, Smax, Hq, Hkv, D = 2, 64, 4, 2, 16
q = jax.random.normal(key, (B, 1, Hq, D))
kn = jax.random.normal(jax.random.PRNGKey(3), (B, 1, Hkv, D))
vn = jax.random.normal(jax.random.PRNGKey(4), (B, 1, Hkv, D))
ck = jax.random.normal(jax.random.PRNGKey(5), (B, Smax, Hkv, D))
cv = jax.random.normal(jax.random.PRNGKey(6), (B, Smax, Hkv, D))
idx = jnp.int32(37)
with mesh:
    out, nck, ncv = jax.jit(lambda *a: decode_attention_sharded(
        *a, mesh=mesh, batch_axes=("data",), seq_axes=("model",)))(
        q, kn, vn, ck, cv, idx)
ck_ref = jax.lax.dynamic_update_slice_in_dim(ck, kn, 37, 1)
cv_ref = jax.lax.dynamic_update_slice_in_dim(cv, vn, 37, 1)
want = attention_ref(q, ck_ref, cv_ref, causal=False, kv_len=38)
assert float(jnp.abs(out - want).max()) < 1e-4, float(jnp.abs(out - want).max())
assert float(jnp.abs(nck - ck_ref).max()) == 0.0

# --- paged flash decoding == page-table-gathered reference -------------
from repro.serve.flash_decode import decode_paged_attention_sharded
from repro.kernels.ref import paged_gather, paged_update
P, ps, M = 16, 8, 4                     # pool pages shard 4-way over model
pk = jax.random.normal(jax.random.PRNGKey(7), (P, ps, Hkv, D))
pv = jax.random.normal(jax.random.PRNGKey(8), (P, ps, Hkv, D))
# slot 0 live at pos 19 (page row 2, shared page 5 with slot 1's prefix);
# slot 1 idle (negative sentinel: store drops, output is don't-care)
pt = jnp.array([[3, 5, 9, -1], [5, 2, -1, -1]], jnp.int32)
pidx = jnp.array([19, -2], jnp.int32)
with mesh:
    pout, npk, npv = jax.jit(lambda *a: decode_paged_attention_sharded(
        *a, mesh=mesh, batch_axes=("data",), seq_axes=("model",)))(
        q, kn, vn, pk, pv, pt, pidx)
rpk, rpv = paged_update(pk, pv, kn, vn, pt, pidx)
kg, valid = paged_gather(rpk, pt)
vg, _ = paged_gather(rpv, pt)
pwant = attention_ref(q, kg, vg, causal=False, kv_len=pidx + 1,
                      kv_valid=valid)
assert float(jnp.abs(pout[0] - pwant[0]).max()) < 1e-4
assert float(jnp.abs(npk - rpk).max()) == 0.0   # idle-slot store dropped
assert float(jnp.abs(npv - rpv).max()) == 0.0

# --- width-k speculative verify == page-table-gathered reference -------
from repro.serve.flash_decode import verify_paged_attention_sharded
W = 3
qw = jax.random.normal(jax.random.PRNGKey(9), (B, W, Hq, D))
knw = jax.random.normal(jax.random.PRNGKey(10), (B, W, Hkv, D))
vnw = jax.random.normal(jax.random.PRNGKey(11), (B, W, Hkv, D))
vidx = jnp.array([13, -4], jnp.int32)           # slot 1 idle: stores drop
with mesh:
    vout, vpk, vpv = jax.jit(lambda *a: verify_paged_attention_sharded(
        *a, mesh=mesh, batch_axes=("data",), seq_axes=("model",)))(
        qw, knw, vnw, pk, pv, pt, vidx)
wpk, wpv = paged_update(pk, pv, knw, vnw, pt, vidx)
kg, valid = paged_gather(wpk, pt)
vg, _ = paged_gather(wpv, pt)
vwant = attention_ref(qw, kg, vg, causal=True, q_offset=vidx,
                      kv_len=vidx + W, kv_valid=valid)
assert float(jnp.abs(vout[0] - vwant[0]).max()) < 1e-4
assert float(jnp.abs(vpk - wpk).max()) == 0.0
assert float(jnp.abs(vpv - wpv).max()) == 0.0

# --- mini dry-run lowering on an 8-device mesh -------------------------
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.models.zoo import build_model
from repro.sharding.rules import make_strategy
from repro.train import state as TS
from repro.train.step import make_train_step
from jax.sharding import NamedSharding
cfg = registry.get_config("qwen3-1.7b").smoke()
model = build_model(cfg)
strat = make_strategy("dp_tp", mesh)
step = make_train_step(model, TrainConfig(), strat)
named = lambda t: jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, s), t,
    is_leaf=lambda x: isinstance(x, PS))
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
bspec = {k: NamedSharding(mesh, PS(("data",), None)) for k in batch}
with mesh:
    jitted = jax.jit(step, in_shardings=(named(TS.state_specs(model, strat)), bspec),
                     out_shardings=(named(TS.state_specs(model, strat)), None))
    compiled = jitted.lower(TS.abstract(model), batch).compile()
assert compiled.cost_analysis() is not None
print("SUBPROC_OK")
"""


def test_distributed_pieces_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=420)
    assert "SUBPROC_OK" in out.stdout, out.stderr[-3000:]

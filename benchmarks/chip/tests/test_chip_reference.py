"""The plain float32 reference against the program's model at smoke size.

The program is run with float32 compute here (``dtype="float32"``), so
the two differ only in the order of float32 sums: logits agree to 1e-4
(relative to their scale ~1) and losses to 1e-5. A wrong equation
(norm, rotary half, GQA grouping, MLP kind, tied head) moves them by
1e-2 or more. In bfloat16, as served, the two differ by ~1e-2, which is
what the benchmark's own comparison on the chip is sized for.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, weights
from benchmarks.chip.reference import model as ref

SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256}


def program(arch):
    from repro.configs import registry
    from repro.models.zoo import build_model
    cfg = dataclasses.replace(registry.get_config(arch), dtype="float32",
                              **SMOKE)
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
         if isinstance(getattr(cfg, f.name), (int, float, bool))}
    return build_model(cfg), m


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "starcoder2-7b"])
def test_forward_matches_program(arch):
    model, m = program(arch)
    params = weights.make_params(model.abstract_params(), 3, m["d_model"])
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want, _ = model.forward(params, jnp.asarray(toks)[None])
    got = ref.logits(params, m, ref.hidden(params, m, jnp.asarray(toks)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "starcoder2-7b"])
def test_loss_and_grad_match_program(arch):
    model, m = program(arch)
    params = weights.make_params(model.abstract_params(), 4, m["d_model"])
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, (2, 24)).astype(np.int32)
    labs = rng.integers(0, 256, (2, 24)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labs)}
    (lp, _), gp = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True)(params)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(p, m, batch["tokens"], batch["labels"]))(params)
    assert abs(float(lp) - float(lr)) < 1e-5
    np.testing.assert_allclose(check.leaf_norms(gp), check.leaf_norms(gr),
                               rtol=1e-4)


def test_adamw_reference_matches_program_step():
    """Three steps of the program's train step (float32 throughout)
    against ``check.reference_train``: losses, the first clipped
    gradient and the change after the steps, per leaf, within 1e-4."""
    from repro.configs.base import TrainConfig
    from repro.optim import adamw
    from repro.train import state as TS
    from repro.train.step import make_train_step
    model, m = program("qwen3-1.7b")
    tcd = dict(learning_rate=1e-2, warmup_steps=1, total_steps=10,
               weight_decay=0.1, b1=0.9, b2=0.95, eps=1e-8, grad_clip=1.0,
               remat="none")
    step = jax.jit(make_train_step(model, TrainConfig(**tcd)))
    p0 = weights.make_params(model.abstract_params(), 5, m["d_model"])
    st = TS.TrainState(params=p0, master=p0, opt=adamw.init(p0),
                       step=jnp.zeros((), jnp.int32))
    rng = np.random.default_rng(2)
    bs = [{"tokens": rng.integers(0, 256, (2, 16)).astype(np.int32),
           "labels": rng.integers(0, 256, (2, 16)).astype(np.int32)}
          for _ in range(3)]
    losses, g1 = [], None
    for b in bs:
        st, met = step(st, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(met["loss"]))
        if g1 is None:
            g1 = check.leaf_norms(st.opt.m) / (1 - tcd["b1"])
    change = check.leaf_norms(jax.tree_util.tree_map(jnp.subtract,
                                                     st.master, p0))
    rl, rg, rc = check.reference_train(p0, m, tcd, bs)
    np.testing.assert_allclose(losses, rl, rtol=1e-5)
    np.testing.assert_allclose(g1, rg, rtol=1e-4, atol=1e-9)
    np.testing.assert_allclose(change, rc, rtol=1e-4, atol=1e-9)
    nums = check.train_numbers((np.asarray(losses), g1, change),
                               (rl, rg, rc))
    assert max(nums.values()) < 1e-4

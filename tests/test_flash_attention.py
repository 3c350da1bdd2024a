"""flash_attention.py edge cases the original sweep missed: sequence
lengths that are NOT multiples of block_q/block_k (the padded tail must
be masked, not attended), GQA group ratios > 1 under those ragged
shapes, and bf16 inputs — each against the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention

KEY = jax.random.PRNGKey(42)


def _qkv(B, Sq, Skv, Hq, Hkv, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    return q, k, v


def _check(q, k, v, causal, **kw):
    out = flash_attention(q, k, v, causal=causal, interpret=True, **kw)
    want = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("Sq,Skv", [
    (100, 100),    # not a multiple of either block size
    (33, 97),      # both ragged, primes
    (130, 64),     # q ragged only
    (64, 70),      # kv ragged only
    (1, 100),      # single-row q against ragged kv
])
@pytest.mark.parametrize("causal", [True, False])
def test_ragged_seq_not_block_multiple(Sq, Skv, causal):
    if causal and Sq > Skv:
        pytest.skip("causal ref assumes q suffix-aligned to kv")
    q, k, v = _qkv(2, Sq, Skv, 4, 2, 32, jnp.float32)
    _check(q, k, v, causal, block_q=32, block_k=32)


@pytest.mark.parametrize("Hq,Hkv", [(8, 2), (6, 3), (8, 1)])
def test_gqa_groups_on_ragged_seq(Hq, Hkv):
    q, k, v = _qkv(1, 100, 100, Hq, Hkv, 16, jnp.float32)
    _check(q, k, v, True, block_q=32, block_k=32)


@pytest.mark.parametrize("Sq,Skv,causal", [
    (100, 100, True), (33, 97, False), (96, 96, True),
])
def test_bf16_ragged_and_aligned(Sq, Skv, causal):
    q, k, v = _qkv(2, Sq, Skv, 8, 2, 32, jnp.bfloat16)
    _check(q, k, v, causal, block_q=32, block_k=32)


def test_block_larger_than_seq():
    # whole sequence fits in one (padded) block
    q, k, v = _qkv(1, 20, 20, 4, 4, 32, jnp.float32)
    _check(q, k, v, True, block_q=128, block_k=128)


def test_jit_and_vmap_compose():
    q, k, v = _qkv(2, 100, 100, 4, 2, 16, jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=32, block_k=32))
    out = f(q, k, v)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Sq,causal", [(100, True), (64, False)])
def test_grad_matches_reference(Sq, causal):
    """The custom VJP (Pallas forward lse + flash_xla backward) gives the
    reference attention's gradients."""
    q, k, v = _qkv(2, Sq, Sq, 4, 2, 16, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=32, block_k=32)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)

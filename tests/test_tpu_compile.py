"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at qwen3-1.7b widths (Hq=16,
Hkv=8, D=128, page 16) against a *described* v5e chip and compiles it
with the TPU compiler, which refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, too much VMEM, unpartitionable calls).
The topology is described inside a fixture, so only the worker that
runs this file loads the TPU compiler; where it cannot be described the
tests skip from there.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_prefill import paged_window_attention
from repro.kernels.paged_attention import paged_decode_attention

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

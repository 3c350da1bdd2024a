"""The persistent compilation cache lands where `enable_compile_cache`
says: in ``JAX_COMPILATION_CACHE_DIR`` when it is set, else in the
checkout's ``.jax_cache``. Each case runs in a fresh process on a copy
of the helper whose checkout is a temporary directory."""
import os
import shutil
import subprocess
import sys

import pytest

import repro.runtime.compile_cache as cc

_PROBE = """
from repro.runtime.compile_cache import enable_compile_cache
import jax, jax.numpy as jnp
enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(5)).block_until_ready()
"""


@pytest.fixture
def checkout(tmp_path):
    dst = tmp_path / "src" / "repro" / "runtime"
    dst.mkdir(parents=True)
    shutil.copy(cc.__file__, dst / "compile_cache.py")
    return tmp_path


def _run(checkout, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_one_fixed_place(checkout, env_set):
    env_dir = checkout / "env_cache" if env_set else None
    _run(checkout, env_dir)
    default = checkout / ".jax_cache"
    want, other = (env_dir, default) if env_set else (default, None)
    assert want.is_dir() and any(want.iterdir())
    if other is not None:
        assert not other.exists()

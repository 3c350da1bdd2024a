"""Production mesh builders.

Functions, not module-level constants, so importing never touches jax
device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain the placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes GSPMD partitions automatically (``make_mesh``
    defaults to explicit sharding-in-types axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """(data, model) mesh over this host's devices: data = all of them,
    model = 1 (a single-device mesh for CPU smoke/examples)."""
    return _auto_mesh((len(jax.devices()), 1), ("data", "model"))


def make_elastic_mesh(num_devices: int):
    """Best-effort (data, model) mesh from a surviving device count —
    used by the elastic-restart path (repro.checkpoint.elastic)."""
    model = 16
    while model > 1 and num_devices % model:
        model //= 2
    return _auto_mesh((num_devices // model, model), ("data", "model"))

"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax init)."""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod: (2, 16, 16) = 512 chips ("pod", "data", "model") — the pod
    axis is pure data parallelism across pods."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — the "
            f"dry-run must set --xla_force_host_platform_device_count=512 "
            f"before any jax import")
    return _auto_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CI smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

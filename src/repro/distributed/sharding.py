"""Sharding rules: logical axes -> mesh axes, with divisibility downgrade.

Production meshes (launch/mesh.py):
    single-pod: (16, 16)        axes ("data", "model")
    multi-pod : (2, 16, 16)     axes ("pod", "data", "model")

Logical axes used by the model zoo:
    "residual" -> FSDP over "data" (weights gathered at use)
    "tp"       -> tensor parallel over "model" (heads / mlp hidden / vocab)
    "experts"  -> expert parallel over "model"
    None       -> replicated

The "pod" axis is pure data parallelism: parameter specs never name it, batch
specs include it when present in the mesh.
"""
from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"

# Extents of the production mesh axes. Used for the divisibility downgrade at
# param-def time; a 1-device (smoke) mesh never consults these because smoke
# tests jit without shardings.
PROD_AXIS_SIZES = {POD_AXIS: 2, DATA_AXIS: 16, MODEL_AXIS: 16}

RULES = {
    "residual": DATA_AXIS,
    "tp": MODEL_AXIS,
    "vocab": MODEL_AXIS,
    "experts": MODEL_AXIS,
    None: None,
}


def _axis_extent(mesh_axes: Union[str, Tuple[str, ...]]) -> int:
    if isinstance(mesh_axes, str):
        return PROD_AXIS_SIZES[mesh_axes]
    return int(np.prod([PROD_AXIS_SIZES[a] for a in mesh_axes]))


def pspec(shape: Sequence[int], logical: Sequence[Optional[str]]) -> P:
    """PartitionSpec for ``shape`` given per-dim logical axes.

    A dim whose extent is not divisible by its mesh-axis extent is replicated
    instead (explicit downgrade — never silent padding).
    """
    assert len(shape) == len(logical), (shape, logical)
    out = []
    for dim, name in zip(shape, logical):
        mesh_ax = RULES.get(name, None) if isinstance(name, (str, type(None))) else name
        if mesh_ax is None or dim % _axis_extent(mesh_ax) != 0:
            out.append(None)
        else:
            out.append(mesh_ax)
    return P(*out)


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.axis_names)


def batch_spec(mesh: jax.sharding.Mesh, *trailing) -> P:
    """Spec for a [batch, ...] array: batch over (pod, data)."""
    return P(batch_axes(mesh), *trailing)


def filter_spec(spec: P, mesh: jax.sharding.Mesh) -> P:
    """Drop axes not present in ``mesh`` from a PartitionSpec."""
    names = set(mesh.axis_names)

    def _f(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        kept = tuple(a for a in entry if a in names)
        return kept if kept else None

    return P(*[_f(e) for e in spec])


# --------------------------------------------------------------------------
# Param definitions: build once, derive both init arrays and PartitionSpecs.
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small
    scale: Optional[float] = None
    dtype: str = "float32"

    def spec(self) -> P:
        return pspec(self.shape, self.logical)


def _path_key(key: jax.Array, path: str) -> jax.Array:
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def init_from_defs(defs, key: jax.Array):
    """defs: pytree (nested dicts) of ParamDef -> pytree of arrays."""
    flat, treedef = jax.tree.flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    arrs = []
    for path, d in flat:
        pstr = jax.tree_util.keystr(path)
        dt = jnp.dtype(d.dtype)
        if d.init == "zeros":
            arrs.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            arrs.append(jnp.ones(d.shape, dt))
        else:
            scale = d.scale
            if scale is None:
                fan_in = d.shape[0] if len(d.shape) >= 2 else max(d.shape[-1], 1)
                scale = fan_in ** -0.5
            if d.init == "small":
                scale = 0.02
            arrs.append(scale * jax.random.normal(_path_key(key, pstr), d.shape, dt))
    return jax.tree.unflatten(jax.tree.structure(
        defs, is_leaf=lambda x: isinstance(x, ParamDef)), arrs)


def specs_from_defs(defs):
    return jax.tree.map(lambda d: d.spec(), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


# --------------------------------------------------------------------------
# Mesh context: models call maybe_constrain() on large intermediates; it is a
# no-op unless the launcher installed a mesh (smoke tests run unconstrained).
# --------------------------------------------------------------------------
_CURRENT_MESH: Optional[jax.sharding.Mesh] = None


def set_mesh(mesh: Optional[jax.sharding.Mesh]) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_mesh() -> Optional[jax.sharding.Mesh]:
    return _CURRENT_MESH


def maybe_constrain(x: jax.Array, spec: P) -> jax.Array:
    if _CURRENT_MESH is None:
        return x
    s = filter_spec(spec, _CURRENT_MESH)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(_CURRENT_MESH, s))


def stack_specs(specs, n_leading: int = 1):
    """Prepend ``n_leading`` replicated dims (for scan-stacked segments)."""
    return jax.tree.map(lambda s: P(*((None,) * n_leading), *s), specs,
                        is_leaf=lambda x: isinstance(x, P))

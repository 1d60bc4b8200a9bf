"""QuantizedTensor: the quantized-RESIDENT form of a swapped weight.

PR 2's QuantizedStore cut storage->host bytes ~4x but still materialized a
full fp tensor at swap-in, so device memory and the matmul weight stream
paid full precision. A :class:`QuantizedTensor` is what the store hands the
engine instead when eager dequant is off: the int8 values (or packed int4
carrier) plus the per-channel fp32 scales, as device arrays. Linear
consumers (``models/layers.linear``: MLP in/out, attention qkv/output
projections, shared experts, the LM head) feed it straight to the fused
dequant-matmul kernel (kernels/swap_linear_q.py) so fp never exists for
those weights; every other consumer (conv, einsum expert stacks,
embeddings, SSM input mixes) dequantizes on device at use
(:meth:`dequant` / :func:`materialize`) — the documented fallback.

Registered as a pytree (children: values + scales; aux: logical shape,
dtype, bits) so it passes through jit / tree transforms; tree maps over
parameter trees that must treat it atomically use
``is_leaf=lambda x: isinstance(x, QuantizedTensor)``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# param keys whose consumers route through models/layers.linear — these may
# stay quantized-resident; everything else dequantizes at use (cast_unit_
# params). Covers MLP in/out, attention qkv/out projections, and the head.
FUSED_WEIGHT_KEYS = frozenset({"wi", "wi0", "wi1", "wo", "wq", "wk", "wv",
                               "lm_head"})


@jax.tree_util.register_pytree_node_class
class QuantizedTensor:
    """Per-channel symmetric-quantized tensor (int8, or int4 packed
    two-per-byte into an int8 carrier — see kernels/dequant.pack_int4).

    ``q``      — [R, C] int8 values (bits=8) or [ceil(R/2), C] carrier
                 (bits=4), C = channels = last axis of ``shape``;
    ``scales`` — [C] fp32;
    ``shape``/``dtype`` — the logical tensor this dequantizes back to;
    ``bits``   — 8 or 4.
    """

    __slots__ = ("q", "scales", "shape", "dtype", "bits")

    def __init__(self, q, scales, shape: Tuple[int, ...], dtype: str,
                 bits: int = 8):
        assert bits in (8, 4), bits
        self.q = q
        self.scales = scales
        self.shape = tuple(shape)
        self.dtype = dtype
        self.bits = bits

    # ------------------------------------------------------------ pytree
    def tree_flatten(self):
        return (self.q, self.scales), (self.shape, self.dtype, self.bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    # ------------------------------------------------------------ sizes
    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def rows(self) -> int:
        """Logical rows of the channel grid (prod of all but the last axis)."""
        return math.prod(self.shape[:-1]) if len(self.shape) > 1 else 1

    @property
    def nbytes(self) -> int:
        """Resident cost: quantized payload + scales (what the ledger and
        the VMEM weight stream actually hold)."""
        return int(self.q.nbytes) + int(self.scales.nbytes)

    @property
    def logical_nbytes(self) -> int:
        return math.prod(self.shape) * jnp.dtype(self.dtype).itemsize

    # ------------------------------------------------------------ dequant
    def dequant(self) -> jax.Array:
        """On-device reconstruction to the logical shape/dtype (the
        dequant-then-dense fallback for non-matmul consumers)."""
        from repro.kernels.ops import dequant_int8
        from repro.kernels.ref import unpack_int4_ref
        vals = self.q
        if self.bits == 4:
            vals = unpack_int4_ref(vals, self.rows)
        out = dequant_int8(vals, self.scales, jnp.dtype(self.dtype).type)
        return out.reshape(self.shape)

    def __repr__(self) -> str:
        return (f"QuantizedTensor(int{self.bits}, shape={self.shape}, "
                f"dtype={self.dtype})")


def is_quantized(x) -> bool:
    return isinstance(x, QuantizedTensor)


def materialize(x, dtype: Optional[jnp.dtype] = None):
    """Leaf -> device array: dequantize QuantizedTensors, pass arrays
    through; optionally cast floating leaves to ``dtype``."""
    x = x.dequant() if isinstance(x, QuantizedTensor) else jnp.asarray(x)
    if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(dtype)
    return x


def materialize_tree(tree, dtype: Optional[jnp.dtype] = None):
    """Dequantize every QuantizedTensor leaf of a param tree."""
    return jax.tree.map(lambda a: materialize(a, dtype), tree,
                        is_leaf=is_quantized)


def cast_unit_params(uparams, dtype):
    """Compute-dtype cast for one swapped unit that KEEPS fused-routable
    weights quantized: 2-D matmul weights whose consumers call
    ``layers.linear`` — MLP in/out, attention qkv/output projections,
    shared experts (key in :data:`FUSED_WEIGHT_KEYS`) — stay
    :class:`QuantizedTensor` and stream through ``swap_linear_q``;
    everything else (3-D expert stacks, MLA down/up projections, SSM input
    mixes, norms) follows the seed's cast — dequantized on device, floats
    cast to ``dtype``.
    """
    flat, treedef = jax.tree.flatten_with_path(uparams, is_leaf=is_quantized)
    leaves = []
    for path, leaf in flat:
        if isinstance(leaf, QuantizedTensor):
            key = getattr(path[-1], "key", None) if path else None
            if leaf.ndim == 2 and key in FUSED_WEIGHT_KEYS:
                leaves.append(leaf)
                continue
            leaf = leaf.dequant()
        a = jnp.asarray(leaf)
        if jnp.issubdtype(a.dtype, jnp.floating):
            a = a.astype(dtype)
        leaves.append(a)
    return jax.tree.unflatten(treedef, leaves)

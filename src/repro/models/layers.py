"""Shared layer primitives: norms, RoPE / M-RoPE, MLPs.

:func:`linear` is the precision routing point of the swap path: when a
weight arrives as a :class:`~repro.kernels.qtensor.QuantizedTensor` (the
quant store's fused/lazy mode), the matmul streams the quantized tiles
through the fused dequant-matmul kernel — fp for that weight never exists
in device memory. Plain arrays take the exact jnp path, bit-identical to
the seed.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef
from repro.kernels.qtensor import QuantizedTensor


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = (1.0 + w) if plus_one else w
    return (x * scale).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(dt)


# ------------------------------------------------------------------ rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor`` times
    (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, s) -> np.ndarray:
    """YaRN's rotary frequencies over ``head_dim`` (DeepSeek-V2's published
    form): rotation i < dim/2 keeps its frequency theta^(-2i/dim) below the
    ramp, is divided by ``s.factor`` above it, and blends in between; the
    ramp runs from the rotation that turns ``s.beta_fast`` times over the
    original context to the one that turns ``s.beta_slow`` times."""
    def corr(rotations: float) -> float:
        return (head_dim * math.log(s.original_max_position_embeddings
                                    / (2 * math.pi * rotations))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(s.beta_fast)), 0)
    high = min(math.ceil(corr(s.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(head_dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    base = theta ** (-2.0 * i / head_dim)
    return (base * (ramp / s.factor + (1.0 - ramp))).astype(np.float32)


def rope_cos_sin_scale(s) -> float:
    """Factor YaRN puts on cos and sin (1 where ``mscale`` equals
    ``mscale_all_dim``, as in DeepSeek-V2-Lite); rotation is linear, so the
    rotated vector scales by it."""
    if s is None:
        return 1.0
    return (yarn_mscale(s.factor, s.mscale)
            / yarn_mscale(s.factor, s.mscale_all_dim))


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                mrope_sections: Optional[Tuple[int, int, int]] = None,
                scaling=None) -> jax.Array:
    """positions: [B, S] (rope) or [B, S, 3] (mrope) -> angles [B, S, head_dim/2].
    ``scaling`` (a ``YaRNConfig``) takes the frequencies from
    :func:`yarn_inv_freq`."""
    half = head_dim // 2
    if scaling is not None:
        inv_freq = jnp.asarray(yarn_inv_freq(head_dim, theta, scaling))
    else:
        inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if mrope_sections is None:
        pos = positions.astype(jnp.float32)
        return pos[..., None] * inv_freq
    # M-RoPE (Qwen2-VL): frequency slots are split into (t, h, w) sections,
    # each driven by its own position stream. Static numpy: never traced.
    sec = np.asarray(mrope_sections)
    assert int(sec.sum()) == half, (mrope_sections, half)
    section_id = jnp.asarray(np.repeat(np.arange(3), sec))  # [half]
    pos = positions.astype(jnp.float32)[..., section_id]   # [B, S, half]
    return pos * inv_freq


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: [B, S, H, head_dim]; angles: [B, S, head_dim/2] (neox half-rotation)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(dt)


# ------------------------------------------------------------------ linear
def linear(x: jax.Array, w, b: Optional[jax.Array] = None,
           act: str = "none") -> jax.Array:
    """y = act(x @ w + b), routed by weight representation.

    QuantizedTensor w -> the fused dequant-matmul (``swap_linear_q``): int8
    or packed-int4 tiles are dequantized inside the weight-stream k-loop,
    bias and activation fused at the fp32 flush. Array w -> the plain jnp
    ops the seed used (kept verbatim so exact-store paths stay
    bit-identical). Leading x axes beyond the last are flattened for the
    kernel and restored after.
    """
    if isinstance(w, QuantizedTensor):
        from repro.kernels.ops import swap_linear_q
        lead = x.shape[:-1]
        y = swap_linear_q(x.reshape(-1, x.shape[-1]), w.q, w.scales, b,
                          bits=w.bits, act=act)
        return y.reshape(*lead, y.shape[-1])
    r = x @ w
    if b is not None:
        r = r + b
    if act == "silu":
        r = jax.nn.silu(r)
    elif act == "gelu":
        r = jax.nn.gelu(r, approximate=True)
    return r


# ------------------------------------------------------------------ MLP
def mlp_defs(cfg: ModelConfig, d_in: int, d_hidden: int) -> dict:
    if cfg.act in ("swiglu", "gelu_glu"):
        return {
            "wi0": ParamDef((d_in, d_hidden), ("residual", "tp")),
            "wi1": ParamDef((d_in, d_hidden), ("residual", "tp")),
            "wo": ParamDef((d_hidden, d_in), ("tp", "residual")),
        }
    return {
        "wi": ParamDef((d_in, d_hidden), ("residual", "tp")),
        "wo": ParamDef((d_hidden, d_in), ("tp", "residual")),
    }


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act in ("swiglu", "gelu_glu"):
        gate = linear(x, p["wi0"],
                      act="silu" if cfg.act == "swiglu" else "gelu")
        return linear(gate * linear(x, p["wi1"]), p["wo"])
    h = linear(x, p["wi"])
    if cfg.act == "relu_sq":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h, approximate=True)
    return linear(h, p["wo"])


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)

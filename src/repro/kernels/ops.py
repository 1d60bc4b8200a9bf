"""jit'd public wrappers for the Pallas kernels.

With ``interpret=None`` each wrapper picks its path from the platform JAX
runs on: the Mosaic kernel on a TPU, the ``kernels/ref.py`` oracle on any
other backend. The choice reads ``jax.default_backend()`` and nothing else,
so a TPU run always lowers to a ``tpu_custom_call``. ``interpret=True``
runs the kernel body through the Pallas interpreter (the CPU tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dequant import dequant_int8 as _dequant_int8
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.swap_linear import swap_linear as _swap_linear
from repro.kernels.swap_linear_q import swap_linear_q as _swap_linear_q


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def swap_linear(x, w, b=None, *, act: str = "none",
                interpret: Optional[bool] = None):
    """Weight-streaming linear; interpret=None -> auto (TPU real, CPU ref)."""
    if interpret is None:
        if _on_tpu():
            return _swap_linear(x, w, b, act=act, interpret=False)
        return _ref.swap_linear_ref(x, w, b, act=act)
    return _swap_linear(x, w, b, act=act, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "act", "interpret"))
def swap_linear_q(x, qw, scales, b=None, *, bits: int = 8,
                  act: str = "none", interpret: Optional[bool] = None):
    """Fused dequant-matmul weight stream (int8 / packed int4);
    interpret=None -> auto (TPU real, CPU ref)."""
    if interpret is None:
        if _on_tpu():
            return _swap_linear_q(x, qw, scales, b, bits=bits, act=act,
                                  interpret=False)
        return _ref.swap_linear_q_ref(x, qw, scales, b, act=act, bits=bits)
    return _swap_linear_q(x, qw, scales, b, bits=bits, act=act,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequant_int8(values, scales, out_dtype=jnp.float32, *,
                 interpret: Optional[bool] = None):
    """Dequant-on-swap-in; interpret=None -> auto (TPU real, CPU ref)."""
    if interpret is None:
        if _on_tpu():
            return _dequant_int8(values, scales, out_dtype, interpret=False)
        return _ref.dequant_int8_ref(values, scales, out_dtype)
    return _dequant_int8(values, scales, out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "softcap", "interpret"))
def flash_attention(q, k, v, *, scale=None, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: Optional[bool] = None):
    if interpret is None:
        if _on_tpu():
            return _flash(q, k, v, scale=scale, causal=causal, window=window,
                          softcap=softcap, interpret=False)
        return _ref.flash_attention_ref(q, k, v, scale=scale, causal=causal,
                                        window=window, softcap=softcap)
    return _flash(q, k, v, scale=scale, causal=causal, window=window,
                  softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                    scale=None, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Single-token decode attention through a page table (the paged KV
    serving path); interpret=None -> auto (TPU real, CPU ref)."""
    if interpret is None:
        if _on_tpu():
            return _paged(q, k_pages, v_pages, page_table, seq_lens,
                          scale=scale, window=window, softcap=softcap,
                          interpret=False)
        return _ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                        seq_lens, scale=scale, window=window,
                                        softcap=softcap)
    return _paged(q, k_pages, v_pages, page_table, seq_lens, scale=scale,
                  window=window, softcap=softcap, interpret=interpret)

"""paged_attention: single-token decode attention through a page table.

The paged companion to kernels/flash_attention: instead of a contiguous
[B, S, KV, hd] cache, K/V live in a shared pool of fixed-size token pages
([KV, P, T, hd], see serving/paged_kv.py) and each sequence owns an ordered
page list. The KV-head axis leads so that one (kv, page) block is a
(page_tokens, hd) tile: the TPU lowering wants a block's last two dims
divisible by (8, 128) or equal to the array's, and a KV-minor pool would
hand it a second-minor block dim of 1.

The kernel gathers pages through the SCALAR-PREFETCHED page table
(``pltpu.PrefetchScalarGridSpec``): the index map of the K/V operands reads
``page_table[b, j]`` to pick which physical page the next grid step streams
into VMEM, so the gather costs nothing over the contiguous layout — the DMA
engine simply follows the indirection.

One query token per sequence (decode), grid (B, KV, n_pages) with the page
axis innermost: online (m, l, acc) statistics accumulate across a sequence's
pages exactly like flash_attention accumulates across KV blocks. Slots at or
beyond ``seq_lens[b]`` are masked (pages are zero-padded, the page table is
padded with page 0 — both masked, never read into the softmax), causality is
implicit (the query IS the last cached position), sliding windows skip
fully-out-of-window pages without touching the MXU, and gemma-style logit
softcap is applied pre-masking as in the contiguous kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale: float, window: Optional[int], softcap: Optional[float],
            page_tokens: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    T = page_tokens

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = sl_ref[b]
    q_pos = seq_len - 1                      # the query is the newest token
    # page-level skip: entirely past the sequence, or entirely out of window
    needed = j * T < seq_len
    if window is not None:
        needed = jnp.logical_and(needed,
                                 j * T + T - 1 >= q_pos - (window - 1))

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [T, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        tok = j * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        mask = tok < seq_len                          # causal: q IS the last
        if window is not None:
            mask = jnp.logical_and(mask, (q_pos - tok) < window)
        s = jnp.where(mask, s, NEG_INF)               # [G, T] via broadcast

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, seq_lens: jax.Array, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """q: [B, H, hd] (one decode token per sequence); k/v_pages:
    [KV, P, T, hd] shared page pools; page_table: [B, NP] int32 physical page
    ids (pad with 0 past a sequence's pages); seq_lens: [B] int32 tokens
    valid per sequence (the query token included). Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, P, T, hd_k = k_pages.shape
    assert v_pages.shape == (KV, P, T, hd_k) and hd == hd_k, \
        (q.shape, k_pages.shape, v_pages.shape)
    assert H % KV == 0, (H, KV)
    G = H // KV
    NP = page_table.shape[1]
    assert page_table.shape == (B, NP) and seq_lens.shape == (B,)
    scale = hd ** -0.5 if scale is None else scale

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          softcap=softcap, page_tokens=T, n_pages=NP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, NP),
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, kv, j, pt, sl: (b, kv, 0, 0)),
                pl.BlockSpec((1, 1, T, hd),
                             lambda b, kv, j, pt, sl: (kv, pt[b, j], 0, 0)),
                pl.BlockSpec((1, 1, T, hd),
                             lambda b, kv, j, pt, sl: (kv, pt[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda b, kv, j, pt, sl: (b, kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),     # running max
                pltpu.VMEM((G, 1), jnp.float32),     # running sum
                pltpu.VMEM((G, hd), jnp.float32),    # output accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q.reshape(B, KV, G, hd), k_pages, v_pages)
    return out.reshape(B, H, hd)

"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def swap_linear_ref(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None,
                    act: str = "none") -> jax.Array:
    r = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    if b is not None:
        r = r + b.astype(jnp.float32)
    if act == "silu":
        r = r * jax.nn.sigmoid(r)
    elif act == "gelu":
        r = jax.nn.gelu(r, approximate=True)
    return r.astype(x.dtype)


def dequant_int8_ref(values: jax.Array, scales: jax.Array,
                     out_dtype=jnp.float32) -> jax.Array:
    """values [R, C] int8, scales [C] fp32 -> values * scales[None, :]."""
    return (values.astype(jnp.float32)
            * scales.astype(jnp.float32)[None, :]).astype(out_dtype)


def unpack_int4_ref(carrier: jax.Array, rows: int) -> jax.Array:
    """Traceable inverse of dequant.pack_int4: [Rp, C] int8 carrier ->
    [rows, C] sign-extended values (even row = low nibble, odd = high)."""
    qi = carrier.astype(jnp.int32)
    low = jnp.right_shift(jnp.left_shift(qi, 28), 28)   # sign-extend nibble
    high = jnp.right_shift(qi, 4)                       # arithmetic shift
    out = jnp.stack([low, high], axis=1).reshape(2 * carrier.shape[0],
                                                 carrier.shape[1])
    return out[:rows].astype(jnp.int8)


def swap_linear_q_ref(x: jax.Array, qw: jax.Array, scales: jax.Array,
                      b: Optional[jax.Array] = None, act: str = "none",
                      bits: int = 8) -> jax.Array:
    """Oracle for the fused dequant-matmul: dequantize the whole weight,
    then the plain swap_linear math. qw is [K, N] int8 (bits=8) or the
    [ceil(K/2), N] packed carrier (bits=4); scales is [N] fp32."""
    K = x.shape[-1]
    vals = unpack_int4_ref(qw, K) if bits == 4 else qw
    w = vals.astype(jnp.float32) * scales.astype(jnp.float32)[None, :]
    r = jnp.dot(x.astype(jnp.float32), w)
    if b is not None:
        r = r + b.astype(jnp.float32)
    if act == "silu":
        r = r * jax.nn.sigmoid(r)
    elif act == "gelu":
        r = jax.nn.gelu(r, approximate=True)
    return r.astype(x.dtype)


def wkv6_ref(r: jax.Array, k: jax.Array, v: jax.Array, w_log: jax.Array,
             u: jax.Array) -> jax.Array:
    """Literal per-step WKV6 recurrence. r,k,v,w_log: [BH,S,hd]; u: [BH,hd]."""
    BH, S, hd = r.shape
    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    wf = w_log.astype(jnp.float32)
    uf = u.astype(jnp.float32)

    def step(S_state, xs):
        rt, kt, vt, lwt = xs
        bonus = jnp.sum(rt * (uf * kt), axis=-1, keepdims=True)
        y = jnp.einsum("bk,bkv->bv", rt, S_state) + bonus * vt
        S_new = jnp.exp(lwt)[..., None] * S_state + kt[..., None] * vt[:, None, :]
        return S_new, y

    S0 = jnp.zeros((BH, hd, hd), jnp.float32)
    xs = (rf.swapaxes(0, 1), kf.swapaxes(0, 1), vf.swapaxes(0, 1),
          wf.swapaxes(0, 1))
    _, ys = jax.lax.scan(step, S0, xs)
    return ys.swapaxes(0, 1).astype(r.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        page_table: jax.Array, seq_lens: jax.Array, *,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None) -> jax.Array:
    """Gather-then-attend oracle for kernels/paged_attention: materialize
    each sequence's pages contiguously ([B, NP*T, KV, hd]) and run masked
    single-query attention. q: [B, H, hd]; k/v_pages: [KV, P, T, hd];
    returns [B, H, hd]."""
    B, H, hd = q.shape
    KV, P, T, _ = k_pages.shape
    G = H // KV
    NP = page_table.shape[1]
    scale = hd ** -0.5 if scale is None else scale

    def gather(pages):          # [KV, B, NP, T, hd] -> [B, NP*T, KV, hd]
        return pages[:, page_table].transpose(1, 2, 3, 0, 4).reshape(
            B, NP * T, KV, hd)

    k, v = gather(k_pages), gather(v_pages)
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    tok = jnp.arange(NP * T)[None, :]                     # [1, S]
    q_pos = (seq_lens - 1)[:, None]                       # [B, 1]
    mask = tok < seq_lens[:, None]                        # causal: q is last
    if window is not None:
        mask &= (q_pos - tok) < window
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: Optional[float] = None, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None) -> jax.Array:
    BH, S, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    s = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v.astype(jnp.float32)).astype(q.dtype)

"""A plain float32 reference of DeepSeek-V2(-Lite), from the published
equations (arXiv:2405.04434 and the model's public modeling code), in
``jax.numpy`` with no kernel, cache, batching trick or code of the program.

It reads the program's parameter tree (``Model.init``), the one thing it
shares with the program, and a ``ModelConfig`` for the sizes. Per layer:

    x += W_o attn(rms(x) * ln1)                         (MLA, un-absorbed)
    x += FFN(rms(x) * ln2)

MLA: q = h W_q split into q_nope [H, nd] and q_rope [H, rd]; the latent
c = rms(h W_dkv) * kv_norm, k_nope = c W_uk, v = c W_uv per head, one
k_rope = h W_krope shared by the heads; q_rope and k_rope rotated by YaRN
RoPE (halves rotated: the program's layout, a fixed permutation of the
published interleaved columns); causal softmax over q.k at (nd + rd)^-0.5
times mscale(factor, mscale_all_dim)^2. FFN: a SwiGLU of d_ff on the first
``first_k_dense`` layers; after that a softmax router over every routed
expert, the top-k weights (renormalised only with ``norm_topk_prob``) on
each token's top-k experts, every expert computed for every token and no
token dropped, plus the shared experts. Final norm, then ``lm_head``.

Everything is float32 under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn(cfg):
    """(inv_freq [rd/2], cos/sin scale, softmax scale) of the config."""
    m, s = cfg.mla, cfg.rope_scaling
    dim, theta = m.qk_rope_head_dim, cfg.rope_theta
    i = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if s is None:
        return inv.astype(np.float32), 1.0, scale

    def corr(r):
        return (dim * math.log(s.original_max_position_embeddings
                               / (2 * math.pi * r)) / (2 * math.log(theta)))

    def mscale(f, ms):
        return 1.0 if f <= 1 else 0.1 * ms * math.log(f) + 1.0

    low = max(math.floor(corr(s.beta_fast)), 0)
    high = min(math.ceil(corr(s.beta_slow)), dim - 1)
    ramp = np.clip((i - low) / (max(high - low, 1e-3)), 0.0, 1.0)
    inv = inv / s.factor * ramp + inv * (1.0 - ramp)
    return (inv.astype(np.float32),
            mscale(s.factor, s.mscale) / mscale(s.factor, s.mscale_all_dim),
            scale * mscale(s.factor, s.mscale_all_dim) ** 2)


def _rotate(x, pos, inv, c):
    """Half rotation of x [..., S, heads, rd] at positions pos [S]."""
    ang = pos[:, None].astype(jnp.float32) * inv              # [S, rd/2]
    cos, sin = jnp.cos(ang)[:, None] * c, jnp.sin(ang)[:, None] * c
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(cfg, p, h):
    m = cfg.mla
    B, S, _ = h.shape
    H, nd, rd, vd = (cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                     m.v_head_dim)
    inv, c, scale = yarn(cfg)
    pos = jnp.arange(S)
    q = (h @ p["wq"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], _rotate(q[..., nd:], pos, inv, c)
    lat = _rms(h @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    k_nope = (lat @ p["w_uk"]).reshape(B, S, H, nd)
    v = (lat @ p["w_uv"]).reshape(B, S, H, vd)
    k_rope = _rotate((h @ p["w_krope"])[:, :, None, :], pos, inv, c)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))],
                        -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, H * vd) @ p["wo"]


def _swiglu(h, wi0, wi1, wo):
    return (jax.nn.silu(h @ wi0) * (h @ wi1)) @ wo


def _moe(cfg, p, h):
    """Routed experts (all computed, gated by the top-k) plus shared ones;
    also the top-k experts of every token [B, S, K]."""
    e = cfg.moe
    probs = jax.nn.softmax(h @ p["router"], -1)                # [B,S,E]
    top_w, top_e = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(top_e, e.n_routed) * top_w[..., None], -2)
    ys = jnp.stack([_swiglu(h, p["wi0"][j], p["wi1"][j], p["wo"][j])
                    for j in range(e.n_routed)], -2)          # [B,S,E,D]
    y = jnp.sum(ys * gate[..., None], -2)
    if e.n_shared:
        sp = p["shared"]
        y = y + _swiglu(h, sp["wi0"], sp["wi1"], sp["wo"])
    return y, top_e


def forward(cfg, params, tokens):
    """Logits [B, S, V] of tokens [B, S], and each MoE layer's routes
    {layer: top_e [B, S, K]}."""
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        x = p32["embed"][tokens]
        routes = {}
        layer = 0
        for seg in p32["segments"]:
            n = jax.tree.leaves(seg)[0].shape[0]
            for j in range(n):
                p = jax.tree.map(lambda a: a[j], seg)
                x = x + _mla(cfg, p["attn"], _rms(x, p["ln1"], cfg.norm_eps))
                h = _rms(x, p["ln2"], cfg.norm_eps)
                if layer < cfg.first_k_dense or cfg.moe is None:
                    f = p["ffn"]
                    x = x + _swiglu(h, f["wi0"], f["wi1"], f["wo"])
                else:
                    y, routes[layer] = _moe(cfg, p["ffn"], h)
                    x = x + y
                layer += 1
        x = _rms(x, p32["final_norm"], cfg.norm_eps)
        return x @ p32["lm_head"], routes

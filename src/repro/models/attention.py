"""Attention: GQA/MHA with chunked online-softmax (flash-style in XLA),
causal / sliding-window / softcap / encoder variants, and MLA (DeepSeek-V2)
with an absorbed decode path.

The chunked implementation is the portable oracle for kernels/flash_attention
and the path used under jit on CPU and in the dry-run: KV is scanned in blocks
with running (m, l, acc) statistics, so the [Sq, Skv] score matrix never
materializes at full sequence length — the KV-block swap-through-a-window
structure mirrors the paper's block swapping one level down (see DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef
from repro.models.layers import (apply_rope, linear, rope_angles,
                                 rope_cos_sin_scale, softcap, yarn_mscale)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LARGE_WINDOW = 1 << 30

# §Perf (beyond-paper): explicit flash-decoding over the sequence-sharded KV
# cache. When set to a mesh axis name (and a mesh is installed via
# distributed.sharding.set_mesh), single-token decode updates the cache shard
# LOCALLY and combines per-shard online-softmax statistics with psum instead
# of letting SPMD all-gather the cache every layer. Enabled by the dry-run /
# serving launcher; None keeps the portable jit path (smoke tests).
SHARDED_DECODE_AXIS = None


def _flash_decode_sharded(q, cache_k, cache_v, k_new, v_new, decode_pos,
                          *, axis, batch_axes, scale, window, logit_cap,
                          block_local=None):
    """q [B,1,H,hd]; cache [B,S,KV,hd] sharded on S over ``axis``; k/v_new
    [B,1,KV,hd]. Returns (out [B,1,H,hd], new_cache_k, new_cache_v).

    Inside shard_map each device owns S_loc = S/axis_size cache rows:
      1. write k/v_new into the local shard iff decode_pos lands in it;
      2. compute partial (m, l, acc) over the local rows;
      3. combine with pmax/psum (flash-decoding) — bytes moved per layer are
         O(B*H*hd), not O(B*S*KV*hd).
    """
    from repro.distributed.sharding import get_mesh
    mesh = get_mesh()
    B, _, H, hd = q.shape
    S = cache_k.shape[1]
    KV = cache_k.shape[2]
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    import numpy as _np
    n_shards = int(_np.prod([mesh.shape[a] for a in axes]))
    S_loc = S // n_shards
    bax = tuple(a for a in batch_axes if a in mesh.axis_names)

    def local_fn(qv, ck, cv, kn, vn, pos):
        Bl = qv.shape[0]                     # batch may be data-sharded
        idx = jnp.zeros((), jnp.int32)
        for a in axes:                       # row-major over the seq axes
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        # --- local cache update (no resharding of the DUS) ---
        local = pos - idx * S_loc                       # [B]
        inb = (local >= 0) & (local < S_loc)
        safe = jnp.clip(local, 0, S_loc - 1)

        def upd(c, u, i, ok):
            c2 = jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
            return jnp.where(ok, c2, c)
        ck = jax.vmap(upd)(ck, kn, safe, inb)
        cv = jax.vmap(upd)(cv, vn, safe, inb)

        # --- partial online softmax over the local rows ---
        G = H // KV
        qf = qv.reshape(Bl, KV, G, hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,bskh->bkgs", qf, ck.astype(jnp.float32)) * scale
        s = softcap(s, logit_cap)
        kv_pos = idx * S_loc + jnp.arange(S_loc)
        qp = pos[:, None, None, None]
        kvp = kv_pos[None, None, None, :]
        mask = kvp <= qp
        mask &= (qp - kvp) < window
        if block_local is not None:
            mask &= (qp // block_local) == (kvp // block_local)
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgs,bskh->bkgh", p, cv.astype(jnp.float32))
        # --- combine across shards ---
        m_g = jax.lax.pmax(m, axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axes)
        acc_g = jax.lax.psum(acc * corr[..., None], axes)
        out = (acc_g / jnp.maximum(l_g[..., None], 1e-30))
        return out.reshape(Bl, 1, H, hd).astype(qv.dtype), ck, cv

    from jax.sharding import PartitionSpec as P
    cache_spec = P(bax if bax else None, axes, None, None)
    rep = P(bax if bax else None, None, None, None)
    pos_spec = P(bax if bax else None)
    out, ck, cv = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(rep, cache_spec, cache_spec, rep, rep, pos_spec),
        out_specs=(rep, cache_spec, cache_spec),
        check_vma=False,
    )(q, cache_k, cache_v, k_new, v_new, decode_pos)
    return out, ck, cv


def online_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, kv_valid_len: Optional[jax.Array],
                     *, causal: bool, window, scale: float,
                     logit_cap: Optional[float], chunk: int = 1024,
                     block_local=None) -> jax.Array:
    """q: [B,Sq,H,hd], k/v: [B,Skv,KV,hd], q_pos: [B,Sq] absolute positions.

    ``window`` may be a python int/None or a traced scalar (scanned local/global
    flag); masking is positional: kv position j attends iff
        j <= q_pos (causal)  and  q_pos - j < window  and  j < kv_valid_len.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    vd = v.shape[-1]          # v head dim may differ (MLA absorbed decode)
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    window = LARGE_WINDOW if window is None else window

    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, vd).transpose(1, 0, 2, 3, 4)
    kv_pos = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)

    qp = q_pos[:, :, None, None, None]                       # [B,Sq,1,1,1]
    if kv_valid_len is not None:
        valid_len = kv_valid_len[:, None, None, None, None]  # [B,1,1,1,1]
    else:
        valid_len = None

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, pci = xs                                   # [B,c,KV,hd], [c]
        s = jnp.einsum("bqkgh,bckh->bqkgc", q, kci.astype(jnp.float32)) * scale
        s = softcap(s, logit_cap)
        pc = pci[None, None, None, None, :]                  # [1,1,1,1,c]
        mask = pc < Skv
        if causal:
            mask &= pc <= qp
            mask &= (qp - pc) < window
        if block_local is not None:     # llama4 iRoPE: block-local attention
            mask &= (qp // block_local) == (pc // block_local)
        if valid_len is not None:
            mask &= pc < valid_len
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckh->bqkgh", p, vci.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, vd), jnp.float32)
    if n_chunks == 1:
        (m, l, acc), _ = body((m0, l0, a0), (kc[0], vc[0], kv_pos[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kv_pos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, vd)


# ------------------------------------------------------------------ GQA layer
def gqa_defs(cfg: ModelConfig) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    d = {
        "wq": ParamDef((D, H * hd), ("residual", "tp")),
        "wk": ParamDef((D, KV * hd), ("residual", "tp")),
        "wv": ParamDef((D, KV * hd), ("residual", "tp")),
        "wo": ParamDef((H * hd, D), ("tp", "residual")),
    }
    if cfg.attn_bias:
        d["bq"] = ParamDef((H * hd,), ("tp",), init="zeros")
        d["bk"] = ParamDef((KV * hd,), ("tp",), init="zeros")
        d["bv"] = ParamDef((KV * hd,), ("tp",), init="zeros")
    return d


def attn_scale(cfg: ModelConfig) -> float:
    if cfg.query_pre_attn_scalar is not None:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.resolved_head_dim ** -0.5


def gqa_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Projections + rotary: x [B,S,D] -> q [B,S,H,hd], k/v [B,S,KV,hd]."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(B, S, H, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(B, S, KV, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(B, S, KV, hd)
    if cfg.rope_type != "none":
        sections = cfg.mrope_sections if cfg.rope_type == "mrope" else None
        ang = rope_angles(positions, hd, cfg.rope_theta, sections)
        q, k = apply_rope(q, ang), apply_rope(k, ang)
    return q, k, v


def gqa_apply(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
              is_local, cache: Optional[dict], decode_pos: Optional[jax.Array],
              chunk: int = 1024) -> Tuple[jax.Array, Optional[dict]]:
    """x: [B,S,D]. Train/prefill: cache=None in, returns new cache (k, v).
    Decode: cache={'k','v'} of [B,Smax,KV,hd], decode_pos [B] write index."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = gqa_qkv(cfg, p, x, positions)

    context_parallel = False
    if decode_pos is None:
        from jax.sharding import PartitionSpec as _P
        from repro.distributed.sharding import (MODEL_AXIS, PROD_AXIS_SIZES,
                                                maybe_constrain)
        if H % PROD_AXIS_SIZES[MODEL_AXIS] != 0:
            context_parallel = True
            # Heads don't divide the TP axis (llama4: 40 vs 16). Left alone,
            # SPMD shards the head_dim CONTRACTION and all-reduces the fp32
            # score tensor every KV chunk (measured 21 GB per reduce). Use
            # context parallelism instead: q sharded over sequence on the
            # model axis, the (small, GQA) k/v gathered per device.
            q = maybe_constrain(q, _P(("pod", "data"), "model", None, None))
            k = maybe_constrain(k, _P(("pod", "data"), None, None, None))
            v = maybe_constrain(v, _P(("pod", "data"), None, None, None))

    window = None
    if cfg.sliding_window is not None:
        if cfg.layer_pattern == "swa":
            window = cfg.sliding_window
        else:  # alternating local/global: is_local is a (possibly traced) bool
            window = jnp.where(is_local, cfg.sliding_window, LARGE_WINDOW)
    block_local = None
    if cfg.attn_chunk is not None and cfg.layer_pattern == "chunked":
        # llama4 iRoPE: 3/4 layers attend within attn_chunk-sized blocks
        block_local = jnp.where(is_local, cfg.attn_chunk, LARGE_WINDOW)

    q_pos = positions[..., 0] if cfg.rope_type == "mrope" else positions
    if (cache is not None and decode_pos is not None
            and cfg.layer_pattern == "swa" and cfg.sliding_window is not None
            and cache["k"].shape[1] <= cfg.sliding_window):
        # ring-buffer (windowed) cache: slot = pos % W (§Perf, beyond-paper)
        out, cache = _windowed_decode(q, cache, k, v, decode_pos,
                                      scale=attn_scale(cfg),
                                      logit_cap=cfg.attn_logit_softcap)
        out = linear(out.reshape(B, S, H * hd).astype(x.dtype), p["wo"])
        return out, cache
    if cache is not None and decode_pos is not None:
        if SHARDED_DECODE_AXIS is not None:
            # flash-decoding over the sequence-sharded cache (§Perf)
            from repro.distributed.sharding import get_mesh
            if get_mesh() is not None:
                w = window if window is not None else LARGE_WINDOW
                bl = None
                if cfg.attn_chunk is not None and cfg.layer_pattern == "chunked":
                    bl = jnp.where(is_local, cfg.attn_chunk, LARGE_WINDOW)
                out, ck, cv = _flash_decode_sharded(
                    q, cache["k"], cache["v"], k, v, decode_pos,
                    axis=SHARDED_DECODE_AXIS, batch_axes=("pod", "data"),
                    scale=attn_scale(cfg), window=w,
                    logit_cap=cfg.attn_logit_softcap, block_local=bl)
                out = linear(out.reshape(B, S, H * hd).astype(x.dtype), p["wo"])
                return out, {"k": ck, "v": cv}
        # single-token decode: write k/v at decode_pos, attend over the cache
        upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0, 0)))
        cache = {"k": upd(cache["k"], k, decode_pos),
                 "v": upd(cache["v"], v, decode_pos)}
        k_all, v_all = cache["k"], cache["v"]
        valid = decode_pos + 1
    else:
        k_all, v_all, valid = k, v, None

    out = online_attention(q, k_all, v_all, q_pos, valid, causal=not cfg.is_encoder,
                           window=window, scale=attn_scale(cfg),
                           logit_cap=cfg.attn_logit_softcap, chunk=chunk,
                           block_local=block_local)
    out = linear(out.reshape(B, S, H * hd).astype(x.dtype), p["wo"])
    # NOTE (§Perf iteration B3, REFUTED): constraining the attention output
    # back to batch-only sharding here was hypothesized to stop the shared
    # expert's D-contraction all-reduces, but measured 2331 GB of collectives
    # (vs 692 GB without) — the per-layer re-gather cost more than it saved.
    # Kept out; see EXPERIMENTS.md §Perf.
    new_cache = cache if cache is not None else {"k": k, "v": v}
    return out, new_cache


def paged_window(cfg: ModelConfig, is_local: bool) -> Optional[int]:
    """The static attention window of one layer on the paged decode path
    (the kernel specializes on it); None = global attention."""
    if cfg.sliding_window is not None and (cfg.layer_pattern == "swa"
                                           or is_local):
        return int(cfg.sliding_window)
    return None


def _windowed_decode(q, cache, k_new, v_new, pos, *, scale, logit_cap):
    """Single-token decode against a ring-buffer cache of length W.

    Slot i holds absolute position kv_pos = i + floor((pos - i)/W)*W — the
    newest position congruent to i (negative = not yet written -> masked).
    """
    B, _, H, hd = q.shape
    W, KV = cache["k"].shape[1], cache["k"].shape[2]
    G = H // KV
    slot = pos % W
    upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0)))
    ck = upd(cache["k"], k_new, slot)
    cv = upd(cache["v"], v_new, slot)

    slots = jnp.arange(W)
    kv_pos = slots[None, :] + ((pos[:, None] - slots[None, :]) // W) * W  # [B,W]
    qf = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, ck.astype(jnp.float32)) * scale
    s = softcap(s, logit_cap)
    mask = (kv_pos >= 0)[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, cv.astype(jnp.float32))
    return out.reshape(B, 1, H, hd), {"k": ck, "v": cv}


# ------------------------------------------------------------------ MLA layer
def mla_defs(cfg: ModelConfig) -> dict:
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": ParamDef((D, H * qd), ("residual", "tp")),
        "w_dkv": ParamDef((D, m.kv_lora_rank), ("residual", None)),
        "w_krope": ParamDef((D, m.qk_rope_head_dim), ("residual", None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="ones"),
        "w_uk": ParamDef((m.kv_lora_rank, H * m.qk_nope_head_dim), (None, "tp")),
        "w_uv": ParamDef((m.kv_lora_rank, H * m.v_head_dim), (None, "tp")),
        "wo": ParamDef((H * m.v_head_dim, D), ("tp", "residual")),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """MLA's softmax scale: (qk_nope + qk_rope)^-0.5, times YaRN's
    mscale(factor, mscale_all_dim) squared where the rope is YaRN."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def _mla_project(cfg: ModelConfig, p: dict, x: jax.Array,
                 positions: jax.Array):
    """x [B,S,D] -> q_nope [B,S,H,nd], q_rope [B,S,H,rd] (rotated), the
    normed latent c_kv [B,S,r] and the shared k_rope [B,S,rd] (rotated)."""
    from repro.models.layers import rms_norm
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd = m.qk_nope_head_dim, m.qk_rope_head_dim
    q = linear(x, p["wq"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)    # [B,S,r]
    k_rope = (x @ p["w_krope"]).reshape(B, S, 1, rd)
    ang = rope_angles(positions, rd, cfg.rope_theta,
                      scaling=cfg.rope_scaling)
    c = rope_cos_sin_scale(cfg.rope_scaling)
    q_rope = apply_rope(q_rope, ang)
    k_rope = apply_rope(k_rope, ang)
    if c != 1.0:
        q_rope, k_rope = q_rope * c, k_rope * c
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_paged_qkv(cfg: ModelConfig, p: dict, x: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One decode token per sequence (x [B,1,D]) in absorbed form: the query
    in latent space q = [q_nope W_uk, q_rope] [B, H, r+rd] and the token's
    latent row [c_kv, k_rope] [B, 1, r+rd]. Scores against the latent rows
    are MLA's q.k; the latent rows weighted by the softmax, first r lanes,
    are the values before W_uv (:func:`mla_paged_out`). So decode is MQA
    with one "KV head" of width r+rd."""
    m = cfg.mla
    B = x.shape[0]
    H, nd, r = cfg.n_heads, m.qk_nope_head_dim, m.kv_lora_rank
    q_nope, q_rope, c_kv, k_rope = _mla_project(cfg, p, x, positions)
    w_uk = p["w_uk"].reshape(r, H, nd)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk.astype(q_nope.dtype))
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1)
    lat = jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype)], -1)
    return q[:, 0], lat.reshape(B, 1, r + m.qk_rope_head_dim)


def mla_paged_out(cfg: ModelConfig, p: dict, out: jax.Array,
                  dtype) -> jax.Array:
    """Paged attention's output over the latent rows [B, H, r+rd] (or
    [B, 1, H, r+rd]): its first r lanes through W_uv, then wo -> [B,1,D]."""
    m = cfg.mla
    H, r, vd = cfg.n_heads, m.kv_lora_rank, m.v_head_dim
    B = out.shape[0]
    lat = out.reshape(B, H, -1)[..., :r]
    w_uv = p["w_uv"].reshape(r, H, vd)
    o = jnp.einsum("bhr,rhv->bhv", lat.astype(dtype), w_uv.astype(dtype))
    return linear(o.reshape(B, 1, H * vd), p["wo"])


def mla_apply(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
              cache: Optional[dict], decode_pos: Optional[jax.Array],
              chunk: int = 1024) -> Tuple[jax.Array, Optional[dict]]:
    """MLA. Cache holds the COMPRESSED latents (c_kv, k_rope) — the memory win.
    Prefill: up-project per block. Decode: absorbed attention in latent space
    (W_uk folded into q, W_uv applied after) so per-step FLOPs stay O(r)."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd, r = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim, m.kv_lora_rank
    scale = mla_scale(cfg)
    q_nope, q_rope, c_kv, k_rope = _mla_project(cfg, p, x, positions)

    if cache is not None and decode_pos is not None:
        upd2 = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0)))
        cache = {"c_kv": upd2(cache["c_kv"], c_kv, decode_pos),
                 "k_rope": upd2(cache["k_rope"], k_rope, decode_pos)}
        # absorbed decode: q_nope' = q_nope @ W_uk^T  -> latent space
        w_uk = p["w_uk"].reshape(r, H, nd)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)          # [B,1,H,r]
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)           # [B,1,H,r+rd]
        k_cat = jnp.concatenate([cache["c_kv"][:, :, None, :].astype(q_cat.dtype),
                                 cache["k_rope"][:, :, None, :].astype(q_cat.dtype)],
                                axis=-1)
        q_pos = positions
        out_lat = online_attention(
            q_cat, k_cat, cache["c_kv"][:, :, None, :], q_pos,
            decode_pos + 1, causal=True, window=None, scale=scale,
            logit_cap=None, chunk=chunk)                            # [B,1,H,r]
        w_uv = p["w_uv"].reshape(r, H, vd)
        out = jnp.einsum("bshr,rhv->bshv", out_lat, w_uv)
        out = linear(out.reshape(B, S, H * vd).astype(x.dtype), p["wo"])
        return out, cache

    # train / prefill: materialize k, v from latents for this block
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, H, nd)
    v = (c_kv @ p["w_uv"]).reshape(B, S, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (B, S, H, rd))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = online_attention(qf, k, v, positions, None, causal=not cfg.is_encoder,
                           window=None, scale=scale, logit_cap=None, chunk=chunk)
    out = linear(out.reshape(B, S, H * vd).astype(x.dtype), p["wo"])
    return out, {"c_kv": c_kv, "k_rope": k_rope}

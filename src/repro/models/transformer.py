"""Config-driven model: one stack covering all assigned families.

Structure
---------
The layer list (``cfg.layer_kinds()``) is grouped into *segments* of
consecutive identical kinds; each segment's params are stacked [n, ...] and
executed with ``lax.scan`` (keeps HLO size O(1) in depth — essential for the
512-device dry-run). Per-layer variation that only changes masking (gemma2
local/global) rides through the scan as a scanned boolean. zamba2's shared
attention block is a single param tree applied at every occurrence (never
stacked, never swapped more than once — see DESIGN.md §4).

Modes: "train"/"prefill" run full sequences (SSM chunked forms, chunked
online-softmax attention); "decode" runs one token against a cache.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import (
    ParamDef, init_from_defs, specs_from_defs, stack_specs, pspec,
    maybe_constrain)
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import linear, mlp_apply, mlp_defs, rms_norm, softcap

LOSS_CHUNK = 512        # token chunk for the logsumexp loss (never [T, V] at once)

# Dry-run accounting: XLA HLO cost analysis counts a while-loop body ONCE, so
# with scan-over-layers the reported FLOPs/bytes are ~n_layers too small. The
# dry-run sets this flag to fully unroll LAYER scans (trip count 1) so
# cost_analysis() reflects the whole model. Inner chunk scans (attention KV
# blocks, SSM chunks, the loss) remain rolled — the residual undercount is the
# attention-score term, reported analytically in the roofline (see
# benchmarks/bench_roofline.py).
LAYER_SCAN_UNROLL = False

# §Perf (beyond-paper): ring-buffer KV cache for uniformly sliding-window
# architectures (h2o-danube). The decode cache holds only the last `window`
# positions (slot = pos % window) instead of the full sequence — the SwapNet
# idea applied to the KV cache: the resident working set is the window, not
# the stream. Enabled by the dry-run / serving launcher.
WINDOWED_KV_CACHE = False

# §Perf (beyond-paper): Megatron-style sequence parallelism on the residual
# stream — the per-layer saved activation (the remat carry) is sharded over
# the "model" axis along sequence, cutting saved-residual memory by the TP
# width at the cost of per-layer gathers. Enabled by the dry-run launcher.
SEQ_PARALLEL_RESIDUAL = False


def _windowed_cache_len(cfg: ModelConfig, seq_len: int) -> int:
    if WINDOWED_KV_CACHE and cfg.layer_pattern == "swa" \
            and cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


# ------------------------------------------------------------------ plan
@dataclass(frozen=True)
class Segment:
    kind: str            # dense | moe | mamba2 | rwkv6 | shared_attn
    n: int
    layer_ids: Tuple[int, ...]

    @property
    def scanned(self) -> bool:
        return self.kind != "shared_attn"


def build_plan(cfg: ModelConfig) -> List[Segment]:
    kinds = cfg.layer_kinds()
    plan: List[Segment] = []
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        plan.append(Segment(kinds[i], j - i, tuple(range(i, j))))
        i = j
    return plan


# ------------------------------------------------------------------ defs
def layer_defs(cfg: ModelConfig, kind: str) -> dict:
    D = cfg.d_model
    if kind == "mamba2":
        return ssm_mod.mamba2_defs(cfg)
    if kind == "rwkv6":
        return ssm_mod.rwkv6_defs(cfg)
    d: Dict[str, Any] = {
        "ln1": ParamDef((D,), (None,), init="zeros" if cfg.post_norms else "ones"),
        "ln2": ParamDef((D,), (None,), init="zeros" if cfg.post_norms else "ones"),
        "attn": attn_mod.mla_defs(cfg) if cfg.mla else attn_mod.gqa_defs(cfg),
    }
    if cfg.post_norms:
        d["post_ln1"] = ParamDef((D,), (None,), init="zeros")
        d["post_ln2"] = ParamDef((D,), (None,), init="zeros")
    if kind == "moe":
        d["ffn"] = moe_mod.moe_defs(cfg)
    else:
        d["ffn"] = mlp_defs(cfg, D, cfg.d_ff)
    return d


def model_defs(cfg: ModelConfig) -> Tuple[dict, List[Segment]]:
    plan = build_plan(cfg)
    D, V = cfg.d_model, cfg.vocab_size
    defs: Dict[str, Any] = {"final_norm": ParamDef(
        (D,), (None,), init="zeros" if cfg.post_norms else "ones")}
    if cfg.embed_inputs:
        defs["embed"] = ParamDef((V, D), ("vocab", "residual"), init="small")
    if cfg.d_frontend:
        defs["frontend"] = ParamDef((cfg.d_frontend, D), (None, "residual"))
    if cfg.is_encoder:
        defs["mask_emb"] = ParamDef((D,), (None,), init="small")
    if not cfg.tie_embeddings or not cfg.embed_inputs:
        defs["lm_head"] = ParamDef((D, V), ("residual", "vocab"), init="small")
    if any(s.kind == "shared_attn" for s in plan):
        defs["shared_attn"] = layer_defs(cfg, "dense")
    defs["segments"] = [
        layer_defs(cfg, s.kind) if s.scanned else {} for s in plan]
    return defs, plan


# ------------------------------------------------------------------ layer
def apply_layer(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                positions: jax.Array, is_local, cache, decode_pos,
                mode: str):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba2":
        h0 = cache["h"] if cache is not None else None
        cs = cache["conv"] if cache is not None else None
        if mode == "decode":
            out, (h, conv) = ssm_mod.mamba2_step(cfg, p, x, h0, cs)
        else:
            out, (h, conv) = ssm_mod.mamba2_chunked(cfg, p, x, h0, cs)
        return x + out, {"h": h, "conv": conv}, aux
    if kind == "rwkv6":
        from repro.models.layers import layer_norm
        S0 = cache["S"] if cache is not None else None
        sh1 = cache["shift1"] if cache is not None else None
        sh2 = cache["shift2"] if cache is not None else None
        xn = layer_norm(x, p["ln1_w"], p["ln1_b"], cfg.norm_eps)
        if mode == "decode":
            out, (S, sh1n) = ssm_mod.rwkv6_time_mix_step(cfg, p, xn, S0, sh1)
        else:
            out, (S, sh1n) = ssm_mod.rwkv6_time_mix_chunked(cfg, p, xn, S0, sh1)
        x = x + out
        xn = layer_norm(x, p["ln2_w"], p["ln2_b"], cfg.norm_eps)
        out, sh2n = ssm_mod.rwkv6_channel_mix(cfg, p, xn, sh2)
        return x + out, {"S": S, "shift1": sh1n, "shift2": sh2n}, aux

    # dense / moe / moe_attn / shared_attn transformer block
    h = rms_norm(x, p["ln1"], cfg.norm_eps, plus_one=cfg.post_norms)
    if cfg.mla is not None:
        a_out, new_cache = attn_mod.mla_apply(cfg, p["attn"], h, positions,
                                              cache, decode_pos)
    else:
        a_out, new_cache = attn_mod.gqa_apply(cfg, p["attn"], h, positions,
                                              is_local, cache, decode_pos)
    x, aux = _block_ffn(cfg, kind, p, x, a_out, mode)
    return x, new_cache, aux


def _block_ffn(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
               a_out: jax.Array, mode: str) -> Tuple[Any, jax.Array]:
    """A transformer block after its attention: residual, then the dense or
    routed FFN with its own residual. Returns (x, aux). A ``moe`` layer
    routes drop-free outside training (``moe.moe_serve``); ``moe_attn``,
    the part of a MoE layer before its routed experts (the swapped
    executor's ``layerNNN_moe`` unit), returns the layer's state for its
    expert groups (``moe.begin``) in place of x."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.post_norms:
        a_out = rms_norm(a_out, p["post_ln1"], cfg.norm_eps, plus_one=True)
    x = x + a_out
    h = rms_norm(x, p["ln2"], cfg.norm_eps, plus_one=cfg.post_norms)
    if kind == "moe_attn":
        return moe_mod.begin(cfg, p["ffn"], x, h), aux
    if kind == "moe" and mode != "train":
        return moe_mod.moe_serve(cfg, p["ffn"], x, h), aux
    if kind == "moe":
        f_out, aux = moe_mod.moe_train(cfg, p["ffn"], h)
    else:
        f_out = mlp_apply(cfg, p["ffn"], h)
    if cfg.post_norms:
        f_out = rms_norm(f_out, p["post_ln2"], cfg.norm_eps, plus_one=True)
    return x + f_out, aux


# One layer as one compiled program, with its parameters as arguments: the
# swapped executors run units through this, so a swapped-in unit reuses the
# executable of every earlier unit of its kind and shape and each layer is
# one dispatch, not one per op. On the CPU it also reproduces the
# whole-model program's rounding: applied op by op there, every bf16
# intermediate is rounded on its own, and across 36 layers that drift
# passes the 2e-2 tolerance against ``Model.prefill``. On a TPU v5e the
# logits come out the same either way. Static: cfg, kind, is_local, mode.
apply_layer_jit = jax.jit(apply_layer, static_argnums=(0, 1, 5, 8))


@functools.partial(jax.jit, static_argnums=(4,))
def expert_group(w: dict, carry: dict, lo: jax.Array, routed: jax.Array,
                 last: bool):
    """One expert-group unit of a MoE layer as one compiled program (the
    only one whose name holds ``expert_group``): the layer's state
    ``carry`` (``moe.begin``) with experts [lo, lo + n) of ``w`` added, and
    on the layer's ``last`` group its output x in the state's place. Also
    returns ``routed`` plus how many of the group's experts some token
    routed to, a count kept on the device."""
    n = w["wi0"].shape[0]
    routed = routed + moe_mod.routed_count(carry["top_e"], lo, n)
    carry = moe_mod.group(carry, w, lo)
    return (moe_mod.end(carry) if last else carry), routed


@functools.partial(jax.jit, static_argnums=(0,))
def _paged_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    """The decode token's q [B, H, hd] and k, v [B, KV, hd]; under MLA the
    latent-space q [B, H, r+rd], the token's latent row [B, 1, r+rd] as k,
    and no v (``attention.mla_paged_qkv``)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps, plus_one=cfg.post_norms)
    if cfg.mla is not None:
        q, lat = attn_mod.mla_paged_qkv(cfg, p["attn"], h, positions)
        return q, lat, None
    q, k, v = attn_mod.gqa_qkv(cfg, p["attn"], h, positions)
    return q[:, 0], k[:, 0], v[:, 0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _paged_out(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
               attn: jax.Array):
    """The rest of the layer after paged attention: ``attn`` [B, H, hd]
    (or [B, 1, H, hd]) through ``wo`` (under MLA its first r lanes through
    W_uv first), the residual and the FFN; a ``moe_attn`` unit returns the
    layer's state for its expert groups."""
    B, S, _ = x.shape
    if cfg.mla is not None:
        a_out = attn_mod.mla_paged_out(cfg, p["attn"], attn, x.dtype)
    else:
        a_out = linear(attn.reshape(B, S, -1).astype(x.dtype),
                       p["attn"]["wo"])
    return _block_ffn(cfg, kind, p, x, a_out, "decode")[0]


def apply_layer_paged(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                      positions: jax.Array, is_local: bool, paged):
    """One decode token per sequence ([B, 1, D]) through the paged KV cache.

    ``paged`` is a layer-bound attend hook (``PagedBatchView.bind``): it
    appends the new K/V (under MLA, the latent row) to each sequence's
    pages on the device, then runs paged attention through the page table.
    The projections before it and the FFN after it are compiled programs,
    like :data:`apply_layer_jit`, and nothing between the four waits for
    the device or slices on the host. Returns the layer's output x (the
    layer's state for a ``moe_attn`` unit)."""
    assert x.shape[1] == 1, "paged attention is the single-token decode path"
    q, k, v = _paged_qkv(cfg, p, x, positions)
    if cfg.mla is not None:
        # MQA over the latent rows: one "KV head" of width r+rd, the pool
        # as both keys and values
        out = paged.attend(q, k, None, scale=attn_mod.mla_scale(cfg))
    else:
        out = paged.attend(q, k, v, scale=attn_mod.attn_scale(cfg),
                           window=attn_mod.paged_window(cfg, is_local),
                           softcap=cfg.attn_logit_softcap)
    return _paged_out(cfg, kind, p, x, out)


# ------------------------------------------------------------------ stack
def apply_stack(cfg: ModelConfig, params: dict, plan: List[Segment],
                x: jax.Array, positions: jax.Array, mode: str,
                cache: Optional[list] = None, decode_pos=None,
                remat: bool = False):
    """Returns (x, new_cache_list, aux_sum)."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: List[Any] = []
    for si, seg in enumerate(plan):
        seg_cache = cache[si] if cache is not None else None
        if not seg.scanned:
            x, c_new, aux = apply_layer(
                cfg, "dense", params["shared_attn"], x, positions,
                False, seg_cache, decode_pos, mode)
            new_cache.append(c_new)
            aux_total += aux
            continue
        flags = jnp.asarray([cfg.is_local_layer(i) for i in seg.layer_ids])

        def body(carry, xs, _kind=seg.kind):
            xcur = carry
            if SEQ_PARALLEL_RESIDUAL and mode != "decode":
                xcur = maybe_constrain(
                    xcur, P(("pod", "data"), "model", None))
            lp, flag, c = xs
            xcur, c_new, aux = apply_layer(cfg, _kind, lp, xcur, positions,
                                           flag, c, decode_pos, mode)
            return xcur, (c_new, aux)

        if remat:
            body = jax.checkpoint(body)
        xs = (params["segments"][si], flags, seg_cache)
        x, (c_seg, aux_seg) = jax.lax.scan(
            body, x, xs, unroll=seg.n if LAYER_SCAN_UNROLL else 1)
        new_cache.append(c_seg)
        aux_total += jnp.sum(aux_seg)
    return x, new_cache, aux_total


# ------------------------------------------------------------------ model
class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.defs, self.plan = model_defs(cfg)

    # ---------------- params
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        parts = dict(self.defs)
        seg_defs = parts.pop("segments")
        params = init_from_defs(parts, key)
        segs = []
        for si, (seg, sdefs) in enumerate(zip(self.plan, seg_defs)):
            if not seg.scanned:
                segs.append({})
                continue
            keys = jax.random.split(jax.random.fold_in(key, 1000 + si), seg.n)
            segs.append(jax.vmap(lambda k, d=sdefs: init_from_defs(d, k))(keys))
        params["segments"] = segs
        return params

    def init_serving(self, key: jax.Array) -> dict:
        """Serving weights: :meth:`init`'s values cast to the compute dtype
        (``cfg.dtype``) inside one jitted program, so swap units are stored
        and streamed at serving precision."""
        return jax.jit(lambda k: self.cast(self.init(k)))(key)

    def param_struct(self, dtype: Optional[str] = None) -> dict:
        """ShapeDtypeStruct pytree (no allocation) — dry-run stand-in.
        dtype overrides storage dtype (e.g. 'bfloat16' for serving weights)."""
        is_def = lambda x: isinstance(x, ParamDef)

        def mk(d: ParamDef, lead=()):
            return jax.ShapeDtypeStruct(lead + d.shape,
                                        jnp.dtype(dtype or d.dtype))

        parts = dict(self.defs)
        seg_defs = parts.pop("segments")
        st = jax.tree.map(mk, parts, is_leaf=is_def)
        st["segments"] = [
            jax.tree.map(lambda d, _n=seg.n: mk(d, (_n,)), sdefs, is_leaf=is_def)
            if seg.scanned else {}
            for seg, sdefs in zip(self.plan, seg_defs)]
        return st

    def param_specs(self) -> dict:
        parts = dict(self.defs)
        seg_defs = parts.pop("segments")
        specs = specs_from_defs(parts)
        specs["segments"] = [
            stack_specs(specs_from_defs(d), 1) if s.scanned else {}
            for s, d in zip(self.plan, seg_defs)]
        return specs

    # ---------------- embedding / io
    def _embed(self, params: dict, batch: dict, mode: str) -> Tuple[jax.Array, jax.Array]:
        """Returns (x [B,S,D], positions)."""
        cfg = self.cfg
        if cfg.embed_inputs:
            key = "token" if mode == "decode" else "tokens"
            tokens = batch[key]
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
            if cfg.family == "vlm" and mode != "decode" and "vision_embeds" in batch:
                v = (batch["vision_embeds"] @ params["frontend"]).astype(x.dtype)
                nv = v.shape[1]
                x = jnp.concatenate([v, x[:, nv:]], axis=1)
        else:
            x = (batch["features"] @ params["frontend"]).astype(jnp.dtype(cfg.dtype))
            if cfg.is_encoder and mode == "train" and "mask" in batch:
                x = jnp.where(batch["mask"][..., None],
                              params["mask_emb"].astype(x.dtype), x)
        if cfg.final_logit_softcap is not None:   # gemma-style embed scaling
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

        if "positions" in batch:
            positions = batch["positions"]
        else:
            B, S = x.shape[:2]
            if mode == "decode":
                positions = batch["pos"][:, None]          # [B,1]
            else:
                positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            if cfg.rope_type == "mrope":
                positions = jnp.broadcast_to(positions[..., None],
                                             positions.shape + (3,))
        return x, positions

    def _head(self, params: dict, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
        return softcap(logits, cfg.final_logit_softcap)

    # ---------------- steps
    def cast(self, params: dict) -> dict:
        """Cast float params to the compute dtype (storage stays fp32 in the
        optimizer; fp32-sensitive math upcasts locally)."""
        dt = jnp.dtype(self.cfg.dtype)
        return jax.tree.map(
            lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
            params)

    def forward(self, params: dict, batch: dict, mode: str = "prefill",
                cache=None, remat: bool = False):
        """Full-sequence forward. Returns (hidden, cache, aux)."""
        params = self.cast(params)
        x, positions = self._embed(params, batch, mode)
        decode_pos = batch.get("pos") if mode == "decode" else None
        x, new_cache, aux = apply_stack(
            self.cfg, params, self.plan, x, positions, mode,
            cache=cache, decode_pos=decode_pos, remat=remat)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps,
                     plus_one=self.cfg.post_norms)
        return x, new_cache, aux

    def loss(self, params: dict, batch: dict) -> Tuple[jax.Array, dict]:
        """Token-chunked cross-entropy (never materializes [T, V])."""
        cfg = self.cfg
        h, _, aux = self.forward(params, batch, mode="train", remat=True)
        B, S, D = h.shape
        targets = batch["targets"]
        if cfg.is_encoder:
            weights = batch["mask"].astype(jnp.float32)
        else:
            weights = jnp.ones((B, S), jnp.float32)

        w_head = params.get("lm_head")
        if w_head is None:
            w_head = params["embed"].T
        chunk = min(LOSS_CHUNK, S)
        n_chunks = S // chunk if S % chunk == 0 else 1
        if S % chunk != 0:
            chunk = S
        hc = h.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
        tc = targets.reshape(B, n_chunks, chunk).swapaxes(0, 1)
        wc = weights.reshape(B, n_chunks, chunk).swapaxes(0, 1)

        def body(carry, xs):
            hs, ts, ws = xs
            logits = softcap(hs.astype(jnp.float32) @ w_head.astype(jnp.float32),
                             cfg.final_logit_softcap)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
            nll = (lse - tgt) * ws
            return carry + jnp.sum(nll), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc, wc))
        denom = jnp.maximum(jnp.sum(weights), 1.0)
        loss = total / denom + aux
        return loss, {"loss": loss, "aux": aux, "tokens": denom}

    def prefill(self, params: dict, batch: dict):
        h, cache, _ = self.forward(params, batch, mode="prefill")
        logits = self._head(params, h[:, -1:])
        return logits, cache

    def decode_step(self, params: dict, cache, batch: dict):
        """batch: {'token': [B,1], 'pos': [B]} (+ 'positions' [B,1,3] for mrope)."""
        h, cache, _ = self.forward(params, batch, mode="decode", cache=cache)
        logits = self._head(params, h)
        return logits, cache

    # ---------------- specs (ShapeDtypeStructs for dry-run / engine alloc)
    def cache_struct(self, shape: ShapeConfig) -> list:
        cfg = self.cfg
        B, L = shape.global_batch, _windowed_cache_len(cfg, shape.seq_len)
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        out = []
        for seg in self.plan:
            lead = (seg.n,) if seg.scanned else ()
            kind = "dense" if seg.kind == "shared_attn" else seg.kind
            if kind == "mamba2":
                d_inner, nh, ds = ssm_mod.mamba2_dims(cfg)
                conv_c = d_inner + 2 * ds
                out.append({
                    "h": jax.ShapeDtypeStruct(lead + (B, nh, cfg.ssm.head_dim, ds), jnp.float32),
                    "conv": jax.ShapeDtypeStruct(lead + (B, cfg.ssm.d_conv - 1, conv_c), dt)})
            elif kind == "rwkv6":
                nh, rhd = ssm_mod.rwkv6_dims(cfg)
                out.append({
                    "S": jax.ShapeDtypeStruct(lead + (B, nh, rhd, rhd), jnp.float32),
                    "shift1": jax.ShapeDtypeStruct(lead + (B, 1, cfg.d_model), dt),
                    "shift2": jax.ShapeDtypeStruct(lead + (B, 1, cfg.d_model), dt)})
            elif cfg.mla is not None:
                m = cfg.mla
                out.append({
                    "c_kv": jax.ShapeDtypeStruct(lead + (B, L, m.kv_lora_rank), dt),
                    "k_rope": jax.ShapeDtypeStruct(lead + (B, L, m.qk_rope_head_dim), dt)})
            else:
                out.append({
                    "k": jax.ShapeDtypeStruct(lead + (B, L, KV, hd), dt),
                    "v": jax.ShapeDtypeStruct(lead + (B, L, KV, hd), dt)})
        return out

    def cache_specs(self, shape: ShapeConfig, mesh=None) -> list:
        """PartitionSpecs matching cache_struct. Batch over (pod, data) where
        divisible; the cache sequence dim is sharded over 'model'
        (flash-decoding style) — and over every remaining axis when batch=1
        (long_500k) so no axis idles."""
        cfg = self.cfg
        B = shape.global_batch
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh \
            else {"data": 16, "model": 16}
        cand = tuple(a for a in ("pod", "data") if a in axis_sizes)
        bsz = int(np.prod([axis_sizes[a] for a in cand])) if cand else 1
        if cand and B % bsz == 0 and B > 1:
            batch_ax, seq_extra = cand, ()
        elif B % axis_sizes.get("data", 16) == 0 and B > 1:
            batch_ax, seq_extra = "data", ()
        else:
            batch_ax = None
            seq_extra = tuple(a for a in ("pod", "data") if a in axis_sizes)
        seq_ax = seq_extra + ("model",) if batch_ax is None else "model"
        out = []
        for seg in self.plan:
            lead = (None,) if seg.scanned else ()
            kind = "dense" if seg.kind == "shared_attn" else seg.kind
            if kind == "mamba2":
                nh = ssm_mod.mamba2_dims(cfg)[1]
                hax = "model" if nh % 16 == 0 else None
                out.append({"h": P(*lead, batch_ax, hax, None, None),
                            "conv": P(*lead, batch_ax, None, None)})
            elif kind == "rwkv6":
                nh = ssm_mod.rwkv6_dims(cfg)[0]
                hax = "model" if nh % 16 == 0 else None
                out.append({"S": P(*lead, batch_ax, hax, None, None),
                            "shift1": P(*lead, batch_ax, None, None),
                            "shift2": P(*lead, batch_ax, None, None)})
            elif cfg.mla is not None:
                out.append({"c_kv": P(*lead, batch_ax, seq_ax, None),
                            "k_rope": P(*lead, batch_ax, seq_ax, None)})
            else:
                out.append({"k": P(*lead, batch_ax, seq_ax, None, None),
                            "v": P(*lead, batch_ax, seq_ax, None, None)})
        return out


def alloc_cache(model: "Model", shape: ShapeConfig) -> list:
    """Materialize a zero-filled decode cache matching cache_struct."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        model.cache_struct(shape))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    if shape.mode == "decode":
        d = {"token": jax.ShapeDtypeStruct((B, 1), i32),
             "pos": jax.ShapeDtypeStruct((B,), i32)}
        if cfg.rope_type == "mrope":
            d["positions"] = jax.ShapeDtypeStruct((B, 1, 3), i32)
        return d
    d = {}
    if cfg.embed_inputs:
        d["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    else:
        d["features"] = jax.ShapeDtypeStruct((B, S, cfg.d_frontend), dt)
    if shape.mode == "train":
        d["targets"] = jax.ShapeDtypeStruct((B, S), i32)
        if cfg.is_encoder:
            d["mask"] = jax.ShapeDtypeStruct((B, S), jnp.bool_)
    if cfg.family == "vlm":
        d["vision_embeds"] = jax.ShapeDtypeStruct((B, cfg.n_vision_tokens,
                                                   cfg.d_frontend), dt)
        d["positions"] = jax.ShapeDtypeStruct((B, S, 3), i32)
    return d


def input_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """PartitionSpecs matching input_specs (batch over (pod, data))."""
    from repro.distributed.sharding import batch_axes, filter_spec
    ba = batch_axes(mesh)
    specs = {}
    for k, v in input_specs(cfg, shape).items():
        trailing = (None,) * (len(v.shape) - 1)
        b = ba if v.shape[0] % int(np.prod([mesh.shape[a] for a in ba])) == 0 else None
        specs[k] = P(b, *trailing)
    return specs

"""The latent-attention mixture-of-experts family: DeepSeek-V2(-Lite).

The interface of ``dense_gqa.py`` (see its docstring), for a model of
multi-head latent attention (MLA) and routed experts. Architecture, from
the DeepSeek-V2 paper (arXiv:2405.04434) and the published config's keys:
token embedding; per layer ``x += attn(rms(x) * ln1)``,
``x += ffn(rms(x) * ln2)``; final RMS norm; logits against an untied
``lm_head``.

* Attention (MLA, no q LoRA): q = h W_q, split per head into q_nope
  (``qk_nope_head_dim``) and q_rope (``qk_rope_head_dim``); the latent
  c = rms(h W_dkv) * kv_norm (``kv_lora_rank``), k_nope = c W_uk and
  v = c W_uv per head (``v_head_dim``), one k_rope = h W_krope shared by
  the heads; q_rope and k_rope rotated by YaRN RoPE (``rope_scaling``;
  halves rotated, as the program lays the columns out: a fixed permutation
  of the published interleaved layout); causal softmax of q.k times
  (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2; the heads' output
  through W_o.
* FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; after them a softmax over the router's logits
  (``n_routed_experts``), each token's top ``num_experts_per_tok`` experts
  (greedy, one group) weighted by their probabilities (renormalised only
  under ``norm_topk_prob``, times ``routed_scaling_factor``), SwiGLU experts
  of ``moe_intermediate_size``, plus ``n_shared_experts`` shared experts
  merged into one SwiGLU of n_shared * moe_intermediate_size.

The reference computes attention un-absorbed and every routed expert for
every token, weighted by the full-softmax routing, dropping none; the
layer's weights are upcast to float32 one layer at a time (the 6.85 GB
model does not fit the chip in float32), everything under
``jax.default_matmul_precision("highest")``.

The counts: prefill un-absorbed (k_nope and v up-projected per token),
decode absorbed (q_nope through W_uk, the output through W_uv; attention
over the r + rope latent row), the FFN at its active experts (top-k and the
shared ones), the head at the positions whose logits the serving path
computes.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from reference import mm, rms, seed_key

MODEL_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "attn_bias",
    "first_k_dense_replace": "first_k_dense",
    "moe_intermediate_size": lambda mc: mc.moe.d_expert,
    "n_routed_experts": lambda mc: mc.moe.n_routed,
    # the program merges the shared experts into one SwiGLU of d_shared
    "n_shared_experts": lambda mc: mc.moe.d_shared // mc.moe.d_expert,
    "num_experts_per_tok": lambda mc: mc.moe.top_k,
    "norm_topk_prob": lambda mc: mc.moe.norm_topk_prob,
    "kv_lora_rank": lambda mc: mc.mla.kv_lora_rank,
    "qk_nope_head_dim": lambda mc: mc.mla.qk_nope_head_dim,
    "qk_rope_head_dim": lambda mc: mc.mla.qk_rope_head_dim,
    "v_head_dim": lambda mc: mc.mla.v_head_dim,
    "q_lora_rank": lambda mc: None,                 # the program has none
    "rope_scaling": lambda mc: None if mc.rope_scaling is None else {
        "type": "yarn", "factor": mc.rope_scaling.factor,
        "original_max_position_embeddings":
            mc.rope_scaling.original_max_position_embeddings,
        "beta_fast": mc.rope_scaling.beta_fast,
        "beta_slow": mc.rope_scaling.beta_slow,
        "mscale": mc.rope_scaling.mscale,
        "mscale_all_dim": mc.rope_scaling.mscale_all_dim},
}

# experts per expert-group program: the program's swap unit of a MoE
# layer's experts (``models/moe.py`` EXPERT_GROUP)
GROUP = 8

# what the program implements of the published config's routing keys
ROUTING = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
           "topk_group": 1, "moe_layer_freq": 1, "routed_scaling_factor": 1}


# ------------------------------------------------------------------ sizes
def dims(model: dict) -> dict:
    """The sizes the reference and the weights need, from a configuration
    file's ``model`` block (Hugging Face key names)."""
    for key, want in ROUTING.items():
        if model.get(key, want) != want:
            raise ValueError(f"{key} {model[key]!r}: the program routes "
                             f"with {key} {want!r}")
    Fe = model["moe_intermediate_size"]
    return {"L": model["num_hidden_layers"], "D": model["hidden_size"],
            "H": model["num_attention_heads"],
            "r": model["kv_lora_rank"], "nd": model["qk_nope_head_dim"],
            "rd": model["qk_rope_head_dim"], "vd": model["v_head_dim"],
            "F": model["intermediate_size"], "Fe": Fe,
            "Fs": model["n_shared_experts"] * Fe,
            "E": model["n_routed_experts"],
            "K": model["num_experts_per_tok"],
            "dense": model["first_k_dense_replace"],
            "norm_topk": bool(model["norm_topk_prob"]),
            "V": model["vocab_size"], "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"]),
            "tied": bool(model["tie_word_embeddings"]),
            "yarn": model.get("rope_scaling")}


# ------------------------------------------------------------------ weights
def _attn_shapes(prefix: str, n: int, d: dict) -> Dict[str, tuple]:
    D, H, r, nd, rd, vd = (d[k] for k in ("D", "H", "r", "nd", "rd", "vd"))
    return {f"{prefix}/ln1": ((n, D), "ones", None),
            f"{prefix}/ln2": ((n, D), "ones", None),
            f"{prefix}/attn/wq": ((n, D, H * (nd + rd)), "normal", D ** -0.5),
            f"{prefix}/attn/w_dkv": ((n, D, r), "normal", D ** -0.5),
            f"{prefix}/attn/w_krope": ((n, D, rd), "normal", D ** -0.5),
            f"{prefix}/attn/kv_norm": ((n, r), "ones", None),
            f"{prefix}/attn/w_uk": ((n, r, H * nd), "normal", r ** -0.5),
            f"{prefix}/attn/w_uv": ((n, r, H * vd), "normal", r ** -0.5),
            f"{prefix}/attn/wo": ((n, H * vd, D), "normal",
                                  (H * vd) ** -0.5)}


def param_shapes(d: dict) -> Dict[str, tuple]:
    """Leaf path -> (shape, init, scale), in the program's layout: segment
    0 the leading dense layers, segment 1 the MoE layers, each leaf with
    the segment's leading layer axis. The router is drawn N(0, 1/D) like
    the other matrices (near zero, its top-k would be near ties)."""
    D, F, Fe, Fs, E, V = (d[k] for k in ("D", "F", "Fe", "Fs", "E", "V"))
    nd_, nm = d["dense"], d["L"] - d["dense"]
    s = {"embed": ((V, D), "normal", 0.02),
         "final_norm": ((D,), "ones", None),
         "lm_head": ((D, V), "normal", 0.02)}
    segs = []
    if nd_:
        segs.append(("dense", nd_))
    if nm:
        segs.append(("moe", nm))
    for i, (kind, n) in enumerate(segs):
        p = f"segments/{i}"
        s.update(_attn_shapes(p, n, d))
        if kind == "dense":
            s[f"{p}/ffn/wi0"] = ((n, D, F), "normal", D ** -0.5)
            s[f"{p}/ffn/wi1"] = ((n, D, F), "normal", D ** -0.5)
            s[f"{p}/ffn/wo"] = ((n, F, D), "normal", F ** -0.5)
            continue
        s[f"{p}/ffn/router"] = ((n, D, E), "normal", D ** -0.5)
        s[f"{p}/ffn/wi0"] = ((n, E, D, Fe), "normal", D ** -0.5)
        s[f"{p}/ffn/wi1"] = ((n, E, D, Fe), "normal", D ** -0.5)
        s[f"{p}/ffn/wo"] = ((n, E, Fe, D), "normal", Fe ** -0.5)
        if Fs:
            s[f"{p}/ffn/shared/wi0"] = ((n, D, Fs), "normal", D ** -0.5)
            s[f"{p}/ffn/shared/wi1"] = ((n, D, Fs), "normal", D ** -0.5)
            s[f"{p}/ffn/shared/wo"] = ((n, Fs, D), "normal", Fs ** -0.5)
    return s


def _nest(flat: Dict[str, jax.Array]) -> dict:
    n_seg = 1 + max(int(p.split("/")[1]) for p in flat
                    if p.startswith("segments/"))
    out: dict = {"segments": [{} for _ in range(n_seg)]}
    for path, a in flat.items():
        parts = path.split("/")
        node = out
        if parts[0] == "segments":
            node, parts = out["segments"][int(parts[1])], parts[2:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = a
    return out


@functools.lru_cache(maxsize=None)
def _maker(model_json: str):
    shapes = param_shapes(dims(json.loads(model_json)))
    names = sorted(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        flat = {}
        for k, name in zip(keys, names):
            shape, init, scale = shapes[name]
            if init == "ones":
                flat[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                flat[name] = (scale * jax.random.normal(k, shape, jnp.float32)
                              ).astype(jnp.bfloat16)
        return _nest(flat)
    return make


def make_params(model: dict, seed: int) -> dict:
    """The model's bf16 weights from ``seed``, made on the device in one
    jitted call."""
    return _maker(json.dumps(model, sort_keys=True))(seed_key(seed))


# ------------------------------------------------------------------ math
def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(d: dict) -> Tuple[jax.Array, float, float]:
    """(inv_freq [rd/2], cos/sin scale, softmax scale) of the rope: YaRN as
    DeepSeek-V2 publishes it, or plain RoPE without ``rope_scaling``."""
    dim, theta = d["rd"], d["theta"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / dim)
    scale = (d["nd"] + d["rd"]) ** -0.5
    s = d["yarn"]
    if not s:
        return inv, 1.0, scale

    def corr(rot):
        return (dim * math.log(s["original_max_position_embeddings"]
                               / (2 * math.pi * rot)) / (2 * math.log(theta)))
    low = max(math.floor(corr(s["beta_fast"])), 0)
    high = min(math.ceil(corr(s["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv * (ramp / s["factor"] + (1.0 - ramp))
    m_all = _mscale(s["factor"], s["mscale_all_dim"])
    return (inv, _mscale(s["factor"], s["mscale"]) / m_all,
            scale * m_all * m_all)


def _rope(x, pos, inv, c):
    """Half-rotation rope at ``pos`` [S]: x [N, S, heads, rd]."""
    ang = pos.astype(jnp.float32)[:, None] * inv          # [S, rd/2]
    cos = (jnp.cos(ang) * c)[None, :, None]
    sin = (jnp.sin(ang) * c)[None, :, None]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale: float, chunk: int):
    """Causal attention in float32, ``chunk`` queries at a time.
    q, k [N, S, H, dq], v [N, S, H, dv] -> [N, S, H * dv]."""
    N, S, H, _ = q.shape
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, chunk):
        hi = min(lo + chunk, S)
        s = jnp.einsum("nqhd,nkhd->nhqk", q[:, lo:hi] * scale, k,
                       precision=jax.lax.Precision.HIGHEST)
        mask = kpos[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("nhqk,nkhd->nqhd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        outs.append(o.reshape(N, hi - lo, -1))
    return jnp.concatenate(outs, axis=1)


def _swiglu(h, wi0, wi1, wo, fp8):
    return mm(jax.nn.silu(mm(h, wi0, fp8)) * mm(h, wi1, fp8), wo, fp8)


class Reference:
    """Layer-by-layer forward of one configuration over given weights."""

    def __init__(self, model: dict, fp8: bool = False, chunk: int = 1024):
        self.d = dims(model)
        self.fp8 = fp8
        self.chunk = chunk
        d = self.d
        inv, c, scale = yarn(d)

        @jax.jit
        def embed(params, tokens):
            return jnp.take(params["embed"], tokens, axis=0).astype(
                jnp.float32)

        def attn(lp, x):
            N, S, _ = x.shape
            H, nd, rd, vd = d["H"], d["nd"], d["rd"], d["vd"]
            at = lp["attn"]
            h = rms(x, lp["ln1"], d["eps"])
            pos = jnp.arange(S)
            q = mm(h, at["wq"], fp8).reshape(N, S, H, nd + rd)
            q = jnp.concatenate([q[..., :nd],
                                 _rope(q[..., nd:], pos, inv, c)], -1)
            lat = rms(mm(h, at["w_dkv"], fp8), at["kv_norm"], d["eps"])
            k_nope = mm(lat, at["w_uk"], fp8).reshape(N, S, H, nd)
            v = mm(lat, at["w_uv"], fp8).reshape(N, S, H, vd)
            k_rope = _rope(mm(h, at["w_krope"], fp8)[:, :, None], pos, inv, c)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (N, S, H, rd))], -1)
            return x + mm(_attention(q, k, v, scale, self.chunk), at["wo"],
                          fp8)

        @jax.jit
        def dense_layer(seg, i, x):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False), seg)
            x = attn(lp, x)
            f = lp["ffn"]
            h = rms(x, lp["ln2"], d["eps"])
            return x + _swiglu(h, f["wi0"], f["wi1"], f["wo"], fp8)

        @jax.jit
        def moe_layer(seg, i, x):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False), seg)
            x = attn(lp, x)
            f = lp["ffn"]
            h = rms(x, lp["ln2"], d["eps"])
            probs = jax.nn.softmax(mm(h, f["router"], fp8), axis=-1)
            top_w, top_e = jax.lax.top_k(probs, d["K"])
            if d["norm_topk"]:
                top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
            gate = jnp.sum(jax.nn.one_hot(top_e, d["E"]) * top_w[..., None],
                           -2)                                # [N, S, E]

            def expert(e, y):
                w = [jax.lax.dynamic_index_in_dim(f[k], e, keepdims=False)
                     for k in ("wi0", "wi1", "wo")]
                return y + gate[..., e, None] * _swiglu(h, *w, fp8)
            y = jax.lax.fori_loop(0, d["E"], expert, jnp.zeros_like(x))
            if d["Fs"]:
                s = f["shared"]
                y = y + _swiglu(h, s["wi0"], s["wi1"], s["wo"], fp8)
            return x + y

        @jax.jit
        def head(params, x, rows):
            """Logits at ``rows`` [N, R] of x [N, S, D] -> [N, R, V]."""
            h = jnp.take_along_axis(x, rows[..., None], axis=1)
            h = rms(h, params["final_norm"], d["eps"])
            w = params["embed"].T if d["tied"] else params["lm_head"]
            return mm(h, w, fp8)

        self._embed, self._head = embed, head
        self._layers = (dense_layer, moe_layer)

    def logits(self, params: dict, tokens: jax.Array,
               rows: jax.Array) -> jax.Array:
        """Logits [N, R, V] at positions ``rows`` [N, R] of ``tokens``
        [N, S] (right padding is harmless: attention is causal)."""
        with jax.default_matmul_precision("highest"):
            x = self._embed(params, tokens)
            segs = params["segments"]
            kinds = ([0] if self.d["dense"] else []) + (
                [1] if self.d["L"] > self.d["dense"] else [])
            for seg, kind in zip(segs, kinds):
                n = jax.tree.leaves(seg)[0].shape[0]
                for i in range(n):
                    x = self._layers[kind](seg, jnp.int32(i), x)
            return self._head(params, x, rows)


# ------------------------------------------------------------------ counts
def attn_proj_params(d: dict) -> int:
    """Weights every token's attention multiplies it by in a prefill (MLA
    un-absorbed: q, the latent and rope down-projections, k_nope and v
    up-projections, o)."""
    D, H, r, nd, rd, vd = (d[k] for k in ("D", "H", "r", "nd", "rd", "vd"))
    return D * H * (nd + rd) + D * (r + rd) + r * H * (nd + vd) + H * vd * D


def ffn_params(d: dict, layer: int) -> int:
    """Weights a token's FFN multiplies it by in ``layer``: the dense
    SwiGLU, or the router, its top-k experts and the shared ones."""
    D = d["D"]
    if layer < d["dense"]:
        return 3 * D * d["F"]
    return D * d["E"] + 3 * D * d["Fe"] * d["K"] + 3 * D * d["Fs"]


def prefill_flops(d: dict, S: int) -> float:
    """One prompt of S tokens: every layer's matmuls for each token at its
    active experts, causal attention (q.k over nope + rope, p.v over v,
    half the square), the head for the last one."""
    mm_ = sum(attn_proj_params(d) + ffn_params(d, i) for i in range(d["L"]))
    attn = 2.0 * S * S * d["H"] * (d["nd"] + d["rd"] + d["vd"]) / 2
    return 2.0 * mm_ * S + d["L"] * attn + 2.0 * d["D"] * d["V"]


def decode_flops(d: dict, ctx: int) -> float:
    """One decode token whose context (itself included) holds ``ctx``
    tokens, absorbed: q, the latent row, q_nope through W_uk, the output
    through W_uv, o; attention over the latent rows (q.k over r + rope,
    p.v over r); the FFN at its active experts; the head."""
    D, H, r, nd, rd, vd = (d[k] for k in ("D", "H", "r", "nd", "rd", "vd"))
    proj = D * H * (nd + rd) + D * (r + rd) + H * nd * r + H * r * vd \
        + H * vd * D
    mm_ = sum(proj + ffn_params(d, i) for i in range(d["L"]))
    attn = 2.0 * ctx * H * ((r + rd) + r)
    return 2.0 * mm_ + d["L"] * attn + 2.0 * D * d["V"]


def paged_attention_cost(d: dict, lens: Iterable[int], itemsize: int = 2
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged decode-attention call over the latent
    pool with context lengths ``lens``: per sequence 2 * H * (576 + 512)
    FLOPs a context token (q.k over the r + rope row, p.v over its r lanes
    that the program keeps); the latent rows read once, as the work needs
    them, q read and the output written once per head."""
    H, W, r = d["H"], d["r"] + d["rd"], d["r"]
    flops = nbytes = 0.0
    for n in lens:
        flops += 2.0 * H * (W + r) * int(n)
        nbytes += itemsize * (int(n) * W + 2 * H * W)
    return flops, nbytes


def expert_group_cost(d: dict, lens: Iterable[int], itemsize: int = 2
                      ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one MoE layer's expert-group programs over a
    decode step of ``len(lens)`` tokens: the routed work (each token
    through its top-k experts), and each group's weights read once with the
    tokens' input and the float32 accumulator read and written once a
    group."""
    B = len(list(lens))
    D, Fe, E, K = d["D"], d["Fe"], d["E"], d["K"]
    groups = -(-E // min(GROUP, E))
    flops = 2.0 * B * K * 3 * D * Fe
    nbytes = itemsize * E * 3 * D * Fe + groups * B * D * (itemsize + 8)
    return flops, nbytes


def kernel_cost(d: dict, program: str, lens: Iterable[int]
                ) -> Optional[Tuple[float, float]]:
    """(FLOPs, bytes) of ``program`` over one decode step: the paged
    attention kernel once a layer, the expert-group programs once a group
    of every MoE layer."""
    lens = list(lens)
    if program == "paged_attention":
        flops, nbytes = paged_attention_cost(d, lens)
        return d["L"] * flops, d["L"] * nbytes
    if program == "expert_group":
        flops, nbytes = expert_group_cost(d, lens)
        n = d["L"] - d["dense"]
        return n * flops, n * nbytes
    return None

"""The dense GQA decoder family: Qwen2, Llama and Mistral-type models.

A family module holds everything the harness knows of one kind of model,
found by name from a configuration file's ``"family"`` key
(``bench/families/<name>.py``; this one where the key is absent). It
imports nothing of the program. It provides:

* ``MODEL_FIELDS``: configuration-file key -> the program's
  ``ModelConfig`` attribute, or a function of it, that ``check_model``
  (``serving.py``) holds the file's ``model`` block to;
* ``dims(model)``: the sizes the rest needs, from the file's ``model``
  block; the harness itself reads ``V`` (the vocabulary the traffic draws
  from) and ``tied`` (whether the store keeps the head as its own table);
* ``param_shapes(d)``, ``make_params(model, seed)``: the weights, bf16,
  on the device, in the layout the program's ``Model`` takes;
* ``Reference(model, fp8, chunk).logits(params, tokens, rows)``: the plain
  forward pass, layer by layer, in float32 at the highest precision, or
  with float8 matmul inputs (the control);
* ``prefill_flops(d, S)``, ``decode_flops(d, ctx)``: operations a prompt
  and a decode token need;
* ``kernel_cost(d, program, lens)``: (FLOPs, bytes) of the device program
  ``program`` over one decode step with context lengths ``lens``, summed
  over the layers that call it, or None for a program the family does not
  know.

Architecture, from the published Qwen2 / Llama / Mistral description:
token embedding; per layer ``x += attn(rms(x) * ln1)``,
``x += mlp(rms(x) * ln2)`` with GQA attention (optional q/k/v bias,
half-rotation RoPE, causal, optional sliding window) and a SwiGLU MLP;
final RMS norm; logits against ``lm_head`` or the tied embedding.

The counts are copied from the program's analytic count
(``configs/flops.py``, ``analytic_flops_per_device``), for batch 1 and
with one change: the head is counted only for the positions whose logits
the serving path computes (the last position of a prefill, each decode
token), not for every position.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from reference import mm, rms, seed_key

MODEL_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                "head_dim": "resolved_head_dim", "rope_theta": "rope_theta",
                "rms_norm_eps": "norm_eps",
                "tie_word_embeddings": "tie_embeddings",
                "attention_bias": "attn_bias",
                "sliding_window": lambda mc: (mc.sliding_window
                                              if mc.layer_pattern == "swa"
                                              else None)}


# ------------------------------------------------------------------ sizes
def dims(model: dict) -> dict:
    """The sizes the reference and the weights need, from a configuration
    file's ``model`` block (Hugging Face key names)."""
    D = model["hidden_size"]
    H = model["num_attention_heads"]
    return {"L": model["num_hidden_layers"], "D": D, "H": H,
            "KV": model["num_key_value_heads"],
            "hd": model.get("head_dim") or D // H,
            "F": model["intermediate_size"], "V": model["vocab_size"],
            "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"]),
            "tied": bool(model["tie_word_embeddings"]),
            "bias": bool(model.get("attention_bias", False)),
            "window": model.get("sliding_window")}


# ------------------------------------------------------------------ weights
def param_shapes(d: dict) -> Dict[str, tuple]:
    """Leaf path -> (shape, init, scale). Layer leaves carry the leading
    layer axis of the program's stacked segment."""
    L, D, H, KV, hd, F, V = (d[k] for k in ("L", "D", "H", "KV", "hd", "F",
                                            "V"))
    s = {"embed": ((V, D), "normal", 0.02),
         "final_norm": ((D,), "ones", None),
         "segments/0/ln1": ((L, D), "ones", None),
         "segments/0/ln2": ((L, D), "ones", None),
         "segments/0/attn/wq": ((L, D, H * hd), "normal", D ** -0.5),
         "segments/0/attn/wk": ((L, D, KV * hd), "normal", D ** -0.5),
         "segments/0/attn/wv": ((L, D, KV * hd), "normal", D ** -0.5),
         "segments/0/attn/wo": ((L, H * hd, D), "normal", (H * hd) ** -0.5),
         "segments/0/ffn/wi0": ((L, D, F), "normal", D ** -0.5),
         "segments/0/ffn/wi1": ((L, D, F), "normal", D ** -0.5),
         "segments/0/ffn/wo": ((L, F, D), "normal", F ** -0.5)}
    if d["bias"]:
        s["segments/0/attn/bq"] = ((L, H * hd), "normal", 0.02)
        s["segments/0/attn/bk"] = ((L, KV * hd), "normal", 0.02)
        s["segments/0/attn/bv"] = ((L, KV * hd), "normal", 0.02)
    if not d["tied"]:
        s["lm_head"] = ((D, V), "normal", 0.02)
    return s


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {"segments": [{}]}
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
def _rope(x, pos, theta):
    """Half-rotation RoPE: x [N, S, heads, hd], pos [S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window, chunk: int):
    """Causal GQA attention in float32, ``chunk`` queries at a time.
    q [N, S, H, hd], k/v [N, S, KV, hd] -> [N, S, H * hd]."""
    N, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(N, S, KV, G, hd) * hd ** -0.5
    kpos = jnp.arange(S)
    outs = []
    for lo in range(0, S, chunk):
        hi = min(lo + chunk, S)
        s = jnp.einsum("nqkgh,nskh->nkgqs", q[:, lo:hi], k,
                       precision=jax.lax.Precision.HIGHEST)
        qpos = jnp.arange(lo, hi)[:, None]
        mask = kpos[None, :] <= qpos
        if window is not None:
            mask &= (qpos - kpos[None, :]) < window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nkgqs,nskh->nqkgh", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        outs.append(o.reshape(N, hi - lo, H * hd))
    return jnp.concatenate(outs, axis=1)


class Reference:
    """Layer-by-layer forward of one configuration over given weights."""

    def __init__(self, model: dict, fp8: bool = False, chunk: int = 1024):
        self.d = dims(model)
        self.fp8 = fp8
        self.chunk = chunk
        d = self.d

        @jax.jit
        def embed(params, tokens):
            return jnp.take(params["embed"], tokens, axis=0).astype(
                jnp.float32)

        @jax.jit
        def layer(seg, i, x):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False), seg)
            N, S, _ = x.shape
            at = lp["attn"]
            h = rms(x, lp["ln1"], d["eps"])
            q = mm(h, at["wq"], fp8)
            k = mm(h, at["wk"], fp8)
            v = mm(h, at["wv"], fp8)
            if d["bias"]:
                q = q + at["bq"].astype(jnp.float32)
                k = k + at["bk"].astype(jnp.float32)
                v = v + at["bv"].astype(jnp.float32)
            pos = jnp.arange(S)
            q = _rope(q.reshape(N, S, d["H"], d["hd"]), pos, d["theta"])
            k = _rope(k.reshape(N, S, d["KV"], d["hd"]), pos, d["theta"])
            v = v.reshape(N, S, d["KV"], d["hd"])
            a = _attention(q, k, v, d["window"], self.chunk)
            x = x + mm(a, at["wo"], fp8)
            h = rms(x, lp["ln2"], d["eps"])
            f = lp["ffn"]
            g = jax.nn.silu(mm(h, f["wi0"], fp8)) * mm(h, f["wi1"], fp8)
            return x + mm(g, f["wo"], fp8)

        @jax.jit
        def head(params, x, rows):
            """Logits at ``rows`` [N, R] of x [N, S, D] -> [N, R, V]."""
            h = jnp.take_along_axis(x, rows[..., None], axis=1)
            h = rms(h, params["final_norm"], d["eps"])
            w = params["embed"].T if d["tied"] else params["lm_head"]
            return mm(h, w, fp8)

        self._embed, self._layer, self._head = embed, layer, head

    def logits(self, params: dict, tokens: jax.Array,
               rows: jax.Array) -> jax.Array:
        """Logits [N, R, V] at positions ``rows`` [N, R] of ``tokens``
        [N, S] (right padding is harmless: attention is causal)."""
        x = self._embed(params, tokens)
        seg = params["segments"][0]
        for i in range(self.d["L"]):
            x = self._layer(seg, jnp.int32(i), x)
        return self._head(params, x, rows)


# ------------------------------------------------------------------ counts
def layer_matmul_params(d: dict) -> int:
    """Weights one layer multiplies every token by (q, k, v, o, the MLP)."""
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def _attended(d: dict, ctx: int) -> int:
    w = d.get("window")
    return ctx if w is None else min(ctx, w)


def prefill_flops(d: dict, S: int) -> float:
    """One prompt of S tokens: every layer's matmuls for each token, causal
    attention (QK^T and PV, half the square), the head for the last one."""
    mm = d["L"] * layer_matmul_params(d)
    skv = _attended(d, S)
    attn = 4.0 * S * skv * d["H"] * d["hd"] * d["L"] / 2
    return 2.0 * mm * S + attn + 2.0 * d["D"] * d["V"]


def decode_flops(d: dict, ctx: int) -> float:
    """One decode token whose context (itself included) holds ``ctx``
    tokens."""
    mm = d["L"] * layer_matmul_params(d)
    attn = 4.0 * _attended(d, ctx) * d["H"] * d["hd"] * d["L"]
    return 2.0 * mm + attn + 2.0 * d["D"] * d["V"]


def paged_attention_cost(d: dict, lens: Iterable[int], itemsize: int = 2
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one paged decode-attention call over a batch with
    context lengths ``lens``: per sequence 4 * H * hd * len FLOPs (QK^T and
    PV); it reads K and V of every context token once, and q and writes
    the output once per head."""
    H, KV, hd = d["H"], d["KV"], d["hd"]
    flops = nbytes = 0.0
    for n in lens:
        n = _attended(d, int(n))
        flops += 4.0 * H * hd * n
        nbytes += itemsize * (2 * n * KV * hd + 2 * H * hd)
    return flops, nbytes


def kernel_cost(d: dict, program: str, lens: Iterable[int]
                ) -> Optional[Tuple[float, float]]:
    """(FLOPs, bytes) of ``program`` over one decode step: every layer calls
    the paged-attention kernel once."""
    if program != "paged_attention":
        return None
    flops, nbytes = paged_attention_cost(d, lens)
    return d["L"] * flops, d["L"] * nbytes

"""The benchmark's own weights and its plain reference of the model.

Nothing here imports the program. ``make_params`` builds a dense GQA
decoder's weights from a seed, in the layout the program's ``Model``
takes (one stacked segment of layers), in bf16 on the device, in one
jitted call. The harness hands them to the program; after the window they
are made again from the same seed for the reference, so the reference
takes nothing the program has made.

``Reference`` is the model's forward pass in plain ``jax.numpy``, layer by
layer so that it fits beside nothing else on the chip: float32 from the
bf16 weights, matmuls at the highest precision (the arithmetic of
``chip_smoke.py``'s ``fp32_logits``). With ``fp8=True`` every matmul input,
weights and activations alike, is rounded to float8_e4m3 with a scale per
output channel or per token first: the precision step below the bf16 the
configurations state. Put in the program's place (``control``), it is the
control that the comparison has to fail.

Architecture, from the published Qwen2 / Llama / Mistral description:
token embedding; per layer ``x += attn(rms(x) * ln1)``,
``x += mlp(rms(x) * ln2)`` with GQA attention (optional q/k/v bias,
half-rotation RoPE, causal, optional sliding window) and a SwiGLU MLP;
final RMS norm; logits against ``lm_head`` or the tied embedding.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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


def stored_bytes(params: dict, tied: bool) -> int:
    """What the swap store holds for these weights: every leaf, and for a
    tied head the embedding again as the head unit's own table."""
    total = sum(int(a.nbytes) for a in jax.tree.leaves(params))
    return total + (int(params["embed"].nbytes) if tied else 0)


# ------------------------------------------------------------------ math
def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3 with one scale per slice along ``axis`` (the
    reduced axis of the matmul), and back to float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    """x [..., K] @ w [K, N] in float32 at the highest precision; with
    ``fp8`` both inputs are first rounded to float8 (per token, per output
    channel)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


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
            h = _rms(x, lp["ln1"], d["eps"])
            q = _mm(h, at["wq"], fp8)
            k = _mm(h, at["wk"], fp8)
            v = _mm(h, at["wv"], fp8)
            if d["bias"]:
                q = q + at["bq"].astype(jnp.float32)
                k = k + at["bk"].astype(jnp.float32)
                v = v + at["bv"].astype(jnp.float32)
            pos = jnp.arange(S)
            q = _rope(q.reshape(N, S, d["H"], d["hd"]), pos, d["theta"])
            k = _rope(k.reshape(N, S, d["KV"], d["hd"]), pos, d["theta"])
            v = v.reshape(N, S, d["KV"], d["hd"])
            a = _attention(q, k, v, d["window"], self.chunk)
            x = x + _mm(a, at["wo"], fp8)
            h = _rms(x, lp["ln2"], d["eps"])
            f = lp["ffn"]
            g = jax.nn.silu(_mm(h, f["wi0"], fp8)) * _mm(h, f["wi1"], fp8)
            return x + _mm(g, f["wo"], fp8)

        @jax.jit
        def head(params, x, rows):
            """Logits at ``rows`` [N, R] of x [N, S, D] -> [N, R, V]."""
            h = jnp.take_along_axis(x, rows[..., None], axis=1)
            h = _rms(h, params["final_norm"], d["eps"])
            w = params["embed"].T if d["tied"] else params["lm_head"]
            return _mm(h, w, fp8)

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


# ------------------------------------------------------------------ checks
@jax.jit
def _gaps(ref, tokens, valid):
    """How far below the reference's best logit each chosen token lies."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    return jnp.where(valid, best - got, 0.0)


def served_rows(prompts: Sequence[Sequence[int]],
                outputs: Sequence[Sequence[int]], length: int):
    """Pack each prompt with its served tokens into ``tokens`` [N, length]
    and the positions whose logits chose each served token, ``rows``
    [N, R], with the served tokens [N, R] and a validity mask."""
    N = len(prompts)
    R = max(len(o) for o in outputs)
    tokens = np.zeros((N, length), np.int32)
    rows = np.zeros((N, R), np.int32)
    served = np.zeros((N, R), np.int32)
    valid = np.zeros((N, R), bool)
    for n, (p, o) in enumerate(zip(prompts, outputs)):
        seq = list(p) + list(o)[:-1]
        assert len(seq) <= length, (len(seq), length)
        tokens[n, :len(seq)] = seq
        r = len(o)
        rows[n, :r] = np.arange(len(p) - 1, len(p) - 1 + r)
        served[n, :r] = o
        valid[n, :r] = True
    return tokens, rows, served, valid


def served_gap(model: dict, params: dict, prompts, outputs, length: int,
               control: bool = False) -> Dict[str, float]:
    """Widest gap by which a served token's logit lies below the fp32
    reference's best, over every served token of the given requests.
    With ``control`` the fp8 reference takes the program's place: the
    checked ``served_gap`` is that of the tokens it puts first at the same
    positions, and the program's own reading is kept as
    ``program_served_gap``."""
    tokens, rows, served, valid = served_rows(prompts, outputs, length)
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    served, valid = jnp.asarray(served), jnp.asarray(valid)
    ref = Reference(model).logits(params, tokens, rows)
    out = {"tokens": int(valid.sum())}
    if control:
        out["program_served_gap"] = float(jnp.max(_gaps(ref, served, valid)))
        low = Reference(model, fp8=True).logits(params, tokens, rows)
        served = jnp.argmax(low, axis=-1).astype(jnp.int32)
    out["served_gap"] = float(jnp.max(_gaps(ref, served, valid)))
    return out


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def logit_err(model: dict, params: dict, prompts: List[Sequence[int]],
              answers: List[np.ndarray], control: bool = False
              ) -> Dict[str, float]:
    """Worst max|diff| / max|logit| of each answered last-position logits
    row against the fp32 reference over the same prompt. With ``control``
    the fp8 reference's rows take the program's answers' place in
    ``logit_err``, and the program's own reading is kept as
    ``program_logit_err``."""
    ref32 = Reference(model)
    low = Reference(model, fp8=True) if control else None
    worst = {"logit_err": 0.0}
    if control:
        worst["program_logit_err"] = 0.0
    for p, got in zip(prompts, answers):
        tokens = jnp.asarray(np.asarray(p, np.int32)[None])
        rows = jnp.asarray([[len(p) - 1]], jnp.int32)
        ref = np.asarray(ref32.logits(params, tokens, rows))
        if control:
            worst["program_logit_err"] = max(worst["program_logit_err"],
                                             _rel_err(got, ref))
            got = np.asarray(low.logits(params, tokens, rows))
        worst["logit_err"] = max(worst["logit_err"], _rel_err(got, ref))
    return dict(worst, tokens=len(prompts))

"""What the plain reference of every model family shares.

Nothing here imports the program. A family module
(``bench/families/<name>.py``) makes a configuration's weights from a
seed and holds its forward pass, ``Reference``; this module holds what
does not depend on the family: the seed's PRNG key, the store's size of
a set of weights, the matmul in float32 at the highest precision (the
arithmetic of ``chip_smoke.py``'s ``fp32_logits``) or with float8 inputs,
and the comparisons that decide ``correct``.

With ``fp8=True`` every matmul input, weights and activations alike, is
rounded to float8_e4m3 with a scale per output channel or per token
first: the precision step below the bf16 the configurations state. The
family's ``Reference`` so computed, put in the program's place
(``control``), is the control that the comparison has to fail.

The harness hands the program the family's weights; after the window they
are made again from the same seed for the reference, so the reference
takes nothing the program has made.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


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


def mm(x, w, fp8: bool):
    """x [..., K] @ w [K, N] in float32 at the highest precision; with
    ``fp8`` both inputs are first rounded to float8 (per token, per output
    channel)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


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


def served_gap(family, model: dict, params: dict, prompts, outputs,
               length: int, control: bool = False) -> Dict[str, float]:
    """Widest gap by which a served token's logit lies below the fp32
    reference's best, over every served token of the given requests.
    With ``control`` the fp8 reference takes the program's place: the
    checked ``served_gap`` is that of the tokens it puts first at the same
    positions, and the program's own reading is kept as
    ``program_served_gap``."""
    tokens, rows, served, valid = served_rows(prompts, outputs, length)
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    served, valid = jnp.asarray(served), jnp.asarray(valid)
    ref = family.Reference(model).logits(params, tokens, rows)
    out = {"tokens": int(valid.sum())}
    if control:
        out["program_served_gap"] = float(jnp.max(_gaps(ref, served, valid)))
        low = family.Reference(model, fp8=True).logits(params, tokens,
                                                       rows)
        served = jnp.argmax(low, axis=-1).astype(jnp.int32)
    out["served_gap"] = float(jnp.max(_gaps(ref, served, valid)))
    return out


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def logit_err(family, model: dict, params: dict,
              prompts: List[Sequence[int]], answers: List[np.ndarray],
              control: bool = False) -> Dict[str, float]:
    """Worst max|diff| / max|logit| of each answered last-position logits
    row against the fp32 reference over the same prompt. With ``control``
    the fp8 reference's rows take the program's answers' place in
    ``logit_err``, and the program's own reading is kept as
    ``program_logit_err``."""
    ref32 = family.Reference(model)
    low = family.Reference(model, fp8=True) if control else None
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

"""Operations and bytes the algorithm needs, from shapes alone.

The model arithmetic is copied from the program's analytic count
(``configs/flops.py``, ``analytic_flops_per_device``), for a dense GQA
decoder at batch 1 and with one change: the head is counted only for the
positions whose logits the serving path computes (the last position of a
prefill, each decode token), not for every position. Sizes come from a
configuration file's ``model`` block through ``reference.dims``.
"""
from __future__ import annotations

from typing import Iterable, Tuple


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


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's bound: the larger of compute time and memory time
    at the chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

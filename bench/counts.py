"""The roofline's arithmetic, shared by every model family.

A family (``bench/families/<name>.py``) counts the operations and bytes
its model and kernels need, from shapes alone; this module turns a count
into the least time the chip's peaks (``bench/peaks.json``) allow.
"""
from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's bound: the larger of compute time and memory time
    at the chip's peaks."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

"""Reductions the metric readers in ``bench/metrics/`` share. Each takes
the run's ``Readings`` (``run.py``) and returns a number, or None where
the run holds nothing to read."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import counts
import trace as tr


def tokens_in_window(r) -> List[float]:
    return [t for s in r.sent if s.req is not None
            for t in s.req.output.times if r.in_window(t)]


def token_gaps(r) -> List[float]:
    out = []
    for s in r.sent:
        if s.req is None:
            continue
        ts = [t for t in s.req.output.times if r.in_window(t)]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def delta(r, key: str) -> Optional[float]:
    if key not in r.open or key not in r.close:
        return None
    return r.close[key] - r.open[key]


def stage_share(r, *stages: str) -> float:
    """Share of the window, in %, that the swap engine spent in
    ``stages``."""
    return 100.0 * tr.covered(r.stage(*stages), r.t_open,
                              r.t_close) / r.seconds


def loader_gbps(r) -> Optional[float]:
    busy = tr.covered(r.stage("read", "unpack", "dispatch"), r.t_open,
                      r.t_close)
    moved = delta(r, "bytes_swapped")
    if not busy or not moved:
        return None
    return moved / busy / 1e9


def model_flops(r) -> float:
    """FLOPs of every prefill and decode token processed in the window, as
    the configuration's family counts them."""
    total = 0.0
    for _, _, _, n in r.layer_spans("bench.prefill"):
        total += r.family.prefill_flops(r.d, int(n))
    for _, _, _, lens in r.layer_spans("bench.decode_step"):
        total += sum(r.family.decode_flops(r.d, int(x)) for x in lens)
    return total


def mfu(r) -> Optional[float]:
    f = model_flops(r)
    if not f:
        return None
    return 100.0 * f / (r.seconds * r.peak["bf16_flops_per_s"])


def device_idle(r) -> Optional[float]:
    if r.trace is None:
        return None
    busy = tr.busy(r.trace, r.t_open + r.offset, r.t_close + r.offset)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / r.seconds)


def kernel_roofline(r, program: str) -> Optional[float]:
    """Share, in %, of the roofline the device programs named ``program``
    reached over the decode steps of the window: the least time their calls
    could take at the chip's peaks, by the family's ``kernel_cost``, over
    their device time inside those steps. None where the family has no
    cost for ``program``."""
    steps = r.layer_spans("bench.decode_step")
    costs = [r.family.kernel_cost(r.d, program, lens)
             for _, _, _, lens in steps]
    if r.trace is None or not steps or None in costs:
        return None
    within = [(s + r.offset, e + r.offset) for _, s, e, _ in steps]
    secs = tr.program_seconds(r.trace, r.t_open + r.offset,
                              r.t_close + r.offset + 60.0, within)
    kernel = sum(v for k, v in secs.items() if program in k)
    if kernel <= 0:
        return None
    least = sum(counts.least_seconds(*c, r.peak) for c in costs)
    return 100.0 * least / kernel


def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None

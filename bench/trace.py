"""Reduce a ``jax.profiler`` trace to device busy time, idle gaps and
per-program time, and the interval algebra the readers share.

The trace is the ``*.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData``. A device is busy while one of its
programs runs: the events of the ``XLA Modules`` line of every
``/device:`` plane (on a TPU, a program's weight copies run on a line of
their own, ``Async XLA Ops``, beside its ``XLA Ops``, so the program's
span is what covers both). Without that line the ``XLA Ops`` events are
used, and without a device plane (a CPU run) the events that carry an
``hlo_op`` statistic. Each is named by its program with the ``(id)``
suffix dropped, so ``jit_paged_attention(123)`` counts as
``jit_paged_attention``.
Host spans are the events whose name starts with ``bench.``: the
harness's own ``TraceAnnotation``s. Times are seconds on the trace's
clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_ID = re.compile(r"\(\d+\)$")


def _module_name(name: str) -> str:
    return _ID.sub("", name or "").strip() or "?"


@dataclass
class Trace:
    ops: Dict[str, List[Tuple[float, float, str]]] = field(
        default_factory=dict)           # device -> (start, end, program)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    def spans(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.host if n == name]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(trace_dir: str) -> Trace:
    """The newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    tr = Trace()
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")
                     and any(l.name in ("XLA Modules", "XLA Ops")
                             for l in p.lines)]
    for plane in device_planes:
        lines = {l.name: l for l in plane.lines}
        if "XLA Modules" in lines:
            evs = [(ev.start_ns, ev.duration_ns, ev.name)
                   for ev in lines["XLA Modules"].events]
        else:
            evs = [(ev.start_ns, ev.duration_ns,
                    _stats(ev).get("hlo_module", ev.name))
                   for ev in lines["XLA Ops"].events]
        tr.ops[plane.name] = sorted((s / 1e9, (s + d) / 1e9, _module_name(n))
                                    for s, d, n in evs)
    for plane in pd.planes:
        if plane in device_planes:
            continue
        cpu_ops = []
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith("bench."):
                    tr.host.append((name, ev.start_ns / 1e9,
                                    (ev.start_ns + ev.duration_ns) / 1e9))
                elif not device_planes:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        cpu_ops.append((ev.start_ns / 1e9,
                                        (ev.start_ns + ev.duration_ns) / 1e9,
                                        _module_name(st.get("hlo_module"))))
        if cpu_ops:
            tr.ops[plane.name] = sorted(cpu_ops)
    tr.host.sort(key=lambda h: h[1])
    return tr


# ------------------------------------------------------------ intervals
def union(spans: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if min(e, hi) > max(s, lo)]


def total(spans: Iterable[Interval]) -> float:
    return sum(e - s for s, e in spans)


def covered(spans: Iterable[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the union of ``spans`` covers."""
    return total(clip(union(spans), lo, hi))


def gaps(spans: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no span covers."""
    out, t = [], lo
    for s, e in clip(union(spans), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# ------------------------------------------------------------ reductions
def busy(tr: Trace, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which an operation ran, averaged over the
    devices that ran any."""
    per = [covered([(s, e) for s, e, _ in ops], lo, hi)
           for ops in tr.ops.values()]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def program_seconds(tr: Trace, lo: float, hi: float,
                    within: Optional[Sequence[Interval]] = None
                    ) -> Dict[str, float]:
    """Device seconds per program inside [lo, hi] (and, if given, inside
    the ``within`` spans), summed over devices."""
    out: Dict[str, float] = {}
    win = union(within) if within is not None else [(lo, hi)]
    for ops in tr.ops.values():
        by: Dict[str, List[Interval]] = {}
        for s, e, mod in ops:
            by.setdefault(mod, []).append((s, e))
        for mod, spans in by.items():
            t = sum(covered(spans, max(a, lo), min(b, hi)) for a, b in win
                    if min(b, hi) > max(a, lo))
            if t > 0:
                out[mod] = out.get(mod, 0.0) + t
    return out


def idle_gaps(tr: Trace, lo: float, hi: float) -> List[Interval]:
    """Idle gaps of the first device that ran anything, longest first."""
    for ops in tr.ops.values():
        if ops:
            g = gaps([(s, e) for s, e, _ in ops], lo, hi)
            return sorted(g, key=lambda x: x[0] - x[1])
    return []

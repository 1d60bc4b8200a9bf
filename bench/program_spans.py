#!/usr/bin/env python3
"""The program's own spans in a kept profiler trace, and what they say
about the device's idle gaps.

    python3 bench/program_spans.py <trace_dir> [--seconds <window_s>]

reads the newest ``*.xplane.pb`` under ``trace_dir`` (a run of
``bench/run.py --trace 1 --trace-dir <trace_dir>``) and prints one JSON
object for the window: the ``bench.window`` span, or the ``--seconds``
(the result line's ``device.window_s``) that end where it ends. The span
opens and closes with the window.

* ``idle_gaps``: the ten longest gaps in which the device ran nothing,
  each named by the chain of ``swapnet.*`` spans open at its midpoint on
  the thread that holds the covering ``swapnet.decode_step``,
  ``swapnet.admit`` or ``swapnet.prefill`` (``decode_step>exec>kv.write``);
  a gap no program span covers keeps the harness span around it, or
  ``outside_layers``;
* ``executor_s``: seconds of the window per innermost program span on
  the threads that ran those spans (the time under ``decode_step`` or
  ``admit`` that no nested span claims is its own), and
  ``executor_s_loader_busy``: the part of each during which a loader
  thread was inside a ``swapnet.swap.*`` span;
* ``loader_s``: seconds per innermost span on the other threads;
* ``kv_host_share``: the union of the ``swapnet.kv.write`` spans (the
  dispatch of each layer's K/V append into the pools on the device) over
  the window, in %, the trace's reading of what ``kv_host_share.chat``
  counts.

The program's spans are ``repro.tracing.span`` annotations: events whose
name starts with ``swapnet.`` on the host planes, one line per thread,
their arguments (``step``, ``rid``, ``unit``, ``block``) as event stats.
Times are seconds on the trace's clock.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace as tr                  # noqa: E402

PREFIX = "swapnet."
ROOTS = ("decode_step", "admit", "prefill")
KV_HOST = ("kv.write",)


@dataclass(frozen=True)
class Span:
    thread: Tuple[str, int]         # (plane, line index)
    name: str                       # without the prefix
    start: float
    end: float
    args: Dict[str, object] = field(default_factory=dict, compare=False)


def from_profile(pd) -> List[Span]:
    out = []
    for plane in pd.planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span((plane.name, i), ev.name[len(PREFIX):],
                                    ev.start_ns / 1e9,
                                    (ev.start_ns + ev.duration_ns) / 1e9,
                                    tr._stats(ev)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def newest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Tuple[tr.Trace, List[Span]]:
    """The harness's reduction of the newest trace and the program's spans
    in it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(newest(trace_dir))
    return tr.from_profile(pd), from_profile(pd)


def chain(spans: List[Span], t: float) -> List[Span]:
    """The program spans open at ``t`` on the thread of the decode step,
    admission or prefill pass that covers it, outermost first; empty if
    none covers it."""
    roots = [s for s in spans if s.name in ROOTS and s.start <= t <= s.end]
    if not roots:
        return []
    thread = roots[0].thread
    return [s for s in spans if s.thread == thread and s.start <= t <= s.end]


def label(spans: List[Span], harness: List[tuple], t: float) -> str:
    inner = chain(spans, t)
    if inner:
        return ">".join(s.name for s in inner)
    outer = [n.split(".", 1)[1] for n, s, e in harness if s <= t <= e
             and n != "bench.window"]
    return outer[-1] if outer else "outside_layers"


def segments(spans: List[Span], thread, lo: float,
             hi: float) -> List[Tuple[str, float, float]]:
    """The parts of [lo, hi] in which ``thread`` had a program span open,
    each named by the innermost one (spans on one thread nest, so a sweep
    over their ends with a stack finds it)."""
    mine = [s for s in spans if s.thread == thread]
    ends = sorted([(s.start, 1, -s.end, s.name) for s in mine]
                  + [(s.end, 0, 0.0, s.name) for s in mine])
    out: List[Tuple[str, float, float]] = []
    stack: List[str] = []
    prev = lo
    for x, opens, _, name in ends:
        x = min(max(x, lo), hi)
        if stack and x > prev:
            out.append((stack[-1], prev, x))
        prev = x
        if opens:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    return out


def _by_name(segs) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, a, b in segs:
        out[name] = out.get(name, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv_: -kv_[1]))


def _overlap(disjoint: List[Tuple[float, float]], a: float, b: float
             ) -> float:
    """Seconds of [a, b] that sorted, disjoint intervals cover."""
    i = max(bisect.bisect_right(disjoint, (a, float("inf"))) - 1, 0)
    t = 0.0
    while i < len(disjoint) and disjoint[i][0] < b:
        t += max(0.0, min(b, disjoint[i][1]) - max(a, disjoint[i][0]))
        i += 1
    return t


def window(trace: tr.Trace, seconds: Optional[float] = None
           ) -> Optional[Tuple[float, float]]:
    win = trace.spans("bench.window")
    if not win:
        return None
    lo, hi = win[0]
    return (hi - seconds, hi) if seconds else (lo, hi)


def summary(trace: tr.Trace, spans: List[Span],
            seconds: Optional[float] = None) -> dict:
    lo, hi = window(trace, seconds)
    gaps = tr.idle_gaps(trace, lo, hi)[:10]
    kv = tr.covered([(s.start, s.end) for s in spans if s.name in KV_HOST],
                    lo, hi)
    executors = {s.thread for s in spans if s.name in ROOTS}
    execs = [g for th in executors for g in segments(spans, th, lo, hi)]
    loaders = [g for th in {s.thread for s in spans} - executors
               for g in segments(spans, th, lo, hi)]
    loading = tr.union([(a, b) for name, a, b in loaders
                        if name.startswith("swap.")])
    return {"window_s": hi - lo,
            "busy_s": tr.busy(trace, lo, hi),
            "idle_gaps": [[label(spans, trace.host, (a + b) / 2), b - a]
                          for a, b in gaps],
            "executor_s": _by_name(execs),
            "executor_s_loader_busy": _by_name(
                [(name, 0.0, _overlap(loading, a, b))
                 for name, a, b in execs]),
            "loader_s": _by_name(loaders),
            "kv_host_share": 100.0 * kv / (hi - lo),
            "spans": len(spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("trace_dir")
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window's length, back from its span's end")
    args = ap.parse_args(argv)
    trace, spans = load(args.trace_dir)
    if window(trace) is None:
        print("no bench.window span in the trace", file=sys.stderr)
        return 1
    print(json.dumps(summary(trace, spans, args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

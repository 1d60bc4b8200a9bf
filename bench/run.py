#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for. Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/<config>.json``), the model family it
names (``bench/families/<family>.py``, ``family.py``), its traffic mix
(``bench/traffic/<traffic>.json``), and one reader per metric
(``bench/metrics/<metric>.py``). With ``--trace 0`` the line carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of the
window gives its per-layer metrics and a breakdown.

A run: the family's weights from the seed, the program's serving stack
built on them (``serving.System``), every shape of the mix warmed, the
load started and, once it is steady, ``--seconds`` of window. After the
window the open requests are closed, the program's state freed, and a
sample of the finished requests compared with the family's plain
reference over weights made again from the seed (``reference.py``). The
last lines on standard error, and the ``checks`` key that ends the result
line, give each number compared beside its limit. ``memory_peak_bytes``
is the device's bytes in use at their highest while the window was open,
sampled every 20 ms; the process's peak, which the harness's own weights
set before the program stores them, is ``process_peak_bytes`` beside it.
Without a TPU, or on a device missing from ``bench/peaks.json``, it exits
2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                        # noqa: E402
import gc                                              # noqa: E402
import importlib.util                                  # noqa: E402
import json                                            # noqa: E402
import os                                              # noqa: E402
import sys                                             # noqa: E402
import tempfile                                        # noqa: E402
import threading                                       # noqa: E402
import traceback                                       # noqa: E402
from dataclasses import dataclass                      # noqa: E402
from pathlib import Path                               # noqa: E402
from types import ModuleType                           # noqa: E402
from typing import Callable, Dict, List, Optional      # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
sys.path.insert(0, str(BENCH))

import family as families                              # noqa: E402


# ------------------------------------------------------------ the cell
@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    family: ModuleType              # bench/families/<name>.py
    mix: dict
    metrics: List[dict]             # end-to-end, then per-layer
    root: Path = ROOT

    def reported(self, trace: bool) -> List[dict]:
        return [m for m in self.metrics if m["per_layer"] == trace]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    fam = families.of(cfg, root)
    mix = json.loads((root / "bench" / "traffic" /
                      f"{w['traffic']}.json").read_text())

    def here(m):
        return workload in m.get("workloads", [workload])

    e2e = [dict(m, per_layer=False) for m in bench["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    layer = [dict(m, per_layer=True) for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(workload, int(w["chips"]), cfg, fam, mix, e2e + layer,
                root)


def reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ readings
@dataclass
class Readings:
    """What a metric reader may read: the window on ``time.perf_counter``,
    every request sent, the harness's spans around the layers, the swap
    engine's timeline, counters at the window's open and close, the
    configuration's family and its sizes, and with ``--trace 1`` the
    reduced trace and its clock's offset."""
    t_open: float
    t_close: float
    setup_s: float
    sent: list
    spans: List[tuple]
    timeline: List[tuple]
    open: dict
    close: dict
    d: dict
    peak: dict
    family: ModuleType
    trace: object = None
    offset: float = 0.0             # trace clock - perf_counter

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def layer_spans(self, name: str) -> List[tuple]:
        """Harness spans of ``name`` that started inside the window."""
        return [s for s in self.spans if s[0] == name
                and self.in_window(s[1])]

    def stage(self, *stages: str) -> List[tuple]:
        return [(s, e) for st, s, e in self.timeline if st in stages]


# ------------------------------------------------------------ memory
class MemorySampler:
    """The device's bytes in use, read every ``period`` seconds on a thread
    of its own between ``start`` and ``stop``; ``peak`` is the highest
    reading (-1 where the device keeps no statistics)."""

    def __init__(self, device, period: float = 0.02):
        self.device, self.period = device, period
        self.readings: List[int] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def read(self) -> int:
        return int((self.device.memory_stats() or {}).get("bytes_in_use",
                                                          -1))

    def _loop(self) -> None:
        while True:
            self.readings.append(self.read())
            if self._stop.wait(self.period):
                break

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
        self.readings.append(self.read())

    @property
    def peak(self) -> int:
        return max(self.readings, default=-1)


# ------------------------------------------------------------ checks
def _finished_generate(w) -> list:
    """Every request the program finished, those closed at the window's
    end included: each of their tokens was served greedily."""
    return [s for s in w.sent if s.error is None and s.t_done is not None
            and s.req.output]


def check_generate(cell: Cell, w, params, seed: int, control: bool) -> dict:
    import traffic
    from reference import served_gap
    pick = traffic.sample(_finished_generate(w), cell.mix["sample"], seed,
                          lambda s: len(s.spec.prompt) + len(s.req.output))
    if not pick:
        return {"served_gap": float("inf"), "tokens": 0}
    return served_gap(cell.family, cell.cfg["model"], params,
                      [s.spec.prompt for s in pick],
                      [list(s.req.output) for s in pick],
                      traffic.max_context(cell.mix), control=control)


def check_prefill(cell: Cell, w, params, seed: int, control: bool) -> dict:
    import numpy as np
    import traffic
    from reference import logit_err
    done = [s for s in w.sent if s.error is None and s.answer is not None]
    pick = traffic.sample(done, cell.mix["sample"], seed,
                          lambda s: len(s.spec.prompt))
    if not pick:
        return {"logit_err": float("inf"), "tokens": 0}
    return logit_err(cell.family, cell.cfg["model"], params,
                     [s.spec.prompt for s in pick],
                     [np.asarray(s.answer, np.float32)[0, -1] for s in pick],
                     control=control)


CHECKS = {"generate": check_generate, "prefill": check_prefill}


def resident_prefill_ms(cell: Cell, params, w) -> Dict[int, float]:
    """Latency of the program's whole-model jitted prefill, resident, for
    each prompt length the window sent (the paper's comparison)."""
    import jax
    import jax.numpy as jnp
    import serving
    from repro.models.transformer import Model
    mc = serving.program_config(cell.cfg, cell.family)
    fn = jax.jit(Model(mc).prefill)
    out = {}
    for n in sorted({len(s.spec.prompt) for s in w.sent}):
        batch = {"tokens": jnp.ones((1, n), jnp.int32)}
        jax.block_until_ready(fn(params, batch))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(params, batch))
        out[n] = (time.perf_counter() - t0) * 1e3
    return out


# ------------------------------------------------------------ breakdown
def breakdown(r: Readings) -> dict:
    import trace as tr
    lo, hi = r.t_open + r.offset, r.t_close + r.offset
    progs = tr.program_seconds(r.trace, lo, hi)
    ops = sorted(progs.items(), key=lambda kv: -kv[1])[:10]
    host = [(n, s + r.offset, e + r.offset) for n, s, e, _ in r.spans]
    execs = [(n, s + r.offset, e + r.offset) for n, s, e in r.timeline
             if n in ("wait", "exec")]

    def doing(t: float) -> str:
        outer = [n.split(".", 1)[1] for n, s, e in host if s <= t <= e]
        inner = [n for n, s, e in execs if s <= t <= e]
        return ">".join(outer[-1:] + inner[-1:]) or "outside_layers"

    gaps = tr.idle_gaps(r.trace, lo, hi)[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[doing((a + b) / 2), b - a] for a, b in gaps]}


# ------------------------------------------------------------ one run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             peak: dict, hooks: Optional[dict] = None,
             control: bool = False, trace_dir: Optional[str] = None,
             log=print) -> dict:
    """Everything after the look for a chip: build, warm, window, checks.
    ``hooks`` may wrap the program's prefill or decode step (the tests
    break the timed path there)."""
    import jax
    import serving
    import trace as tr
    fam = cell.family

    counter = serving.CompileCounter()
    t_build = time.perf_counter()
    params = fam.make_params(cell.cfg["model"], seed)
    jax.block_until_ready(params)
    log(f"[bench] weights made in {time.perf_counter() - t_build:.1f}s")
    with tempfile.TemporaryDirectory(prefix="bench-store-") as workdir:
        system = serving.System(cell.cfg, fam, params, workdir,
                                wrap=hooks or {})
        del params
        ratio = system.stored_in_store / system.budget
        log(f"[bench] {cell.cfg['arch']}: stored units "
            f"{system.stored_in_store / 1e6:.1f} MB (predicted "
            f"{system.stored / 1e6:.1f}) = {ratio:.3f}x the "
            f"{system.budget / 1e6:.1f} MB budget; runtime built in "
            f"{system.build_s:.1f}s, {system.sm.plan.n_blocks} blocks, "
            f"m={system.sm.plan.m}"
            + (f", {system.be.kv.max_pages} KV pages of "
               f"{system.be.kv.page_tokens} tokens" if system.be else ""))
        info = serving.warm(system, cell.mix)
        log(f"[bench] warm-up: {json.dumps(info)}")
        stats = jax.devices()[0].memory_stats() or {}
        log(f"[bench] device bytes in use before the window: "
            f"{stats.get('bytes_in_use', -1)} (ledger resident "
            f"{system.rt.ledger.resident})")
        tdir = None
        if trace:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans, not every call
            jax.profiler.start_trace(tdir, profiler_options=opts)
        memory = MemorySampler(jax.devices()[0])
        window = {}

        def on_open():
            # the span starts when its annotation is made: here, at t_open
            window["span"] = jax.profiler.TraceAnnotation("bench.window")
            window["span"].__enter__()
            counter.on = True
            memory.start()

        def on_close():
            window["span"].__exit__(None, None, None)
            memory.stop()
            counter.on = False

        w = serving.drive(system, cell.mix, seed, seconds, on_open, on_close)
        if trace:
            jax.profiler.stop_trace()
        process_peak = int((jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", -1))
        ledger_peak, budget = system.rt.ledger.peak, system.budget
        spans, timeline = list(system.rec.spans), system.timeline()
        system.close()
        del system
        gc.collect()
    setup_s = w.t_open - T_START
    log(f"[bench] window {w.t_close - w.t_open:.3f}s after {setup_s:.1f}s "
        f"of set-up; programs built in the window: {counter.count}; "
        f"requests sent {len(w.sent)}; ledger peak {ledger_peak / 1e6:.1f}"
        f" MB of {budget / 1e6:.1f} MB")
    log(f"[bench] device bytes in use in the window: peak {memory.peak} "
        f"over {len(memory.readings)} readings, first "
        f"{memory.readings[0] if memory.readings else -1}, last "
        f"{memory.readings[-1] if memory.readings else -1}; process peak "
        f"{process_peak}")
    c0, c1 = w.counters_open, w.counters_close
    log("[bench] in the window: " + json.dumps(
        {k: c1[k] - c0[k] for k in ("engine.preemptions", "engine.steps",
                                    "engine.decode_steps", "preemptions")
         if k in c0}))
    if w.late_s:
        log(f"[bench] generator lateness: max {max(w.late_s) * 1e3:.1f} ms")

    r = Readings(w.t_open, w.t_close, setup_s, w.sent, spans, timeline,
                 w.counters_open, w.counters_close,
                 fam.dims(cell.cfg["model"]), peak, fam)
    if trace:
        r.trace = tr.load(tdir)
        win = r.trace.spans("bench.window")
        r.offset = win[0][0] - w.t_open if win else 0.0
    metrics = {}
    for m in cell.reported(trace):
        v = reader(m["name"], cell.root)(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"attempted": len(w.sent),
              "failed": sum(s.error is not None for s in w.sent),
              "metrics": metrics}
    if trace:
        busy = tr.busy(r.trace, w.t_open + r.offset, w.t_close + r.offset)
        result["device_busy"] = {"busy_s": busy, "window_s": r.seconds}
        result["breakdown"] = breakdown(r)
    log(f"[bench] samples: " + json.dumps(sample_counts(r)))

    params = fam.make_params(cell.cfg["model"], seed)
    if trace and cell.mix["requests"] == "prefill":
        log(f"[bench] resident jitted prefill (ms by prompt length): "
            f"{json.dumps(resident_prefill_ms(cell, params, w))}")
    t_ref = time.perf_counter()
    got = CHECKS[cell.mix["requests"]](cell, w, params, seed, control)
    del params
    log(f"[bench] reference over {got['tokens']} answers in "
        f"{time.perf_counter() - t_ref:.1f}s: {json.dumps(got)}")
    limits = cell.cfg["limits"][cell.mix["requests"]]
    checks = {k: {"value": got[k], "limit": lim} for k, lim in limits.items()}
    checks["ledger_peak_mb"] = {"value": ledger_peak / 1e6,
                                "limit": budget / 1e6}
    checks["failed"] = {"value": result["failed"], "limit": 0}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    result["memory_peak_bytes"] = memory.peak
    result["process_peak_bytes"] = process_peak
    result["compiled_in_window"] = counter.count
    result["readings"] = got
    return result


def sample_counts(r: Readings) -> dict:
    toks = [t for s in r.sent if s.req is not None
            for t in s.req.output.times if r.in_window(t)]
    done = [s for s in r.sent if s.t_done and r.in_window(s.t_done)]
    return {"tokens": len(toks), "requests_done": len(done),
            "decode_steps": len(r.layer_spans("bench.decode_step")),
            "prefills": len(r.layer_spans("bench.prefill")),
            "gaps": sum(max(0, sum(r.in_window(t) for t in s.req.output.times)
                            - 1) for s in r.sent if s.req is not None)}


# ------------------------------------------------------------ entry
def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed)")
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout; every program is stored, however quick its compile, and
    nothing is evicted (cells share the checkout's cache)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        print(f"bench: no peaks for device kind {dev.device_kind!r} in "
              f"bench/peaks.json", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"[bench] {cell.name} seed {args.seed} on {dev.device_kind} "
        f"x{len(devices)}; compile cache {CACHE_DIR}")
    try:
        import serving      # noqa: F401  the system under test, or fail here
        import repro        # noqa: F401
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       peaks[dev.device_kind], trace_dir=args.trace_dir,
                       log=log)
    except Exception:               # noqa: BLE001 — no result line
        traceback.print_exc()
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": res["memory_peak_bytes"],
              "process_peak_bytes": res["process_peak_bytes"]}
    if "device_busy" in res:
        device.update(res["device_busy"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

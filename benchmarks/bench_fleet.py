"""Fleet scenario scripted PURELY through the HTTP control plane.

Before the control plane, every fleet scenario was a bespoke
``launch/serve.py`` invocation: the tenant set, budgets, and workload were
frozen at process start, and "a model arrives mid-run" was not expressible
at all. This driver is the counter-example the refactor exists for — one
serving process, resolved from the ``edge-tpu`` deployment profile, driven
end-to-end over plain JSON/HTTP (serving/control_plane.py):

  1. **burst**    — round-robin priority bursts against the two resident
     tenants via ``POST /v1/submit``, latencies polled back from
     ``GET /v1/requests/<rid>`` (the scheduler's own arrival->completion
     ``latency_s``, so polling cadence never distorts the numbers);
  2. **arrival**  — ``POST /v1/models`` registers ``h2o-danube-3-4b`` on
     the live runtime (FusedInf-style: co-tenants keep serving, budgets
     re-planned), then the newcomer's FIRST request measures the cold
     start (jit compile + first swap-in) against its warmed steady state;
  3. **replan**   — ``POST /v1/replan`` with an urgency mix favouring the
     newcomer; the returned per-model block budgets are recorded;
  4. **scrape**   — ``GET /metrics`` (Prometheus text) must agree with
     what the driver observed: completed-request counts per priority
     class, ledger peak under budget, every expected family present;
  5. **shutdown** — ``POST /v1/shutdown`` drains the server; the ledger
     must come back clean.

Standalone CLI for the CI smoke point::

    python -m benchmarks.bench_fleet --smoke
    # -> results/BENCH_fleet.json
"""
from __future__ import annotations

import argparse
import json
import os
import re
import tempfile
import time
import urllib.request

import numpy as np

from benchmarks.common import RESULTS_DIR, emit
from repro.config import resolve_config
from repro.core.serving_scheduler import ServingScheduler
from repro.launch.serve import build_runtime, _make_batches
from repro.serving.control_plane import ControlPlane
from repro.serving.metrics import MetricsRegistry

PROFILE = "edge-tpu"
ARRIVAL_ARCH = "h2o-danube-3-4b"
# families the scrape must serve for the scenario to count as observable
REQUIRED_FAMILIES = (
    "swapnet_ledger_budget_bytes", "swapnet_ledger_peak_bytes",
    "swapnet_cache_hit_rate", "swapnet_requests_completed_total",
    "swapnet_request_latency_seconds", "swapnet_model_up",
    "swapnet_http_requests_total",
)


def _http(base: str, path: str, body=None, timeout: float = 300.0):
    req = urllib.request.Request(
        base + path,
        data=(json.dumps(body).encode() if body is not None else None),
        headers={"Content-Type": "application/json"},
        method="POST" if body is not None else "GET")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        ctype = resp.headers.get("Content-Type", "")
    return raw.decode() if ctype.startswith("text/") else json.loads(raw)


def _poll_done(base: str, rid: int, timeout_s: float = 600.0) -> dict:
    deadline = time.monotonic() + timeout_s
    while True:
        out = _http(base, f"/v1/requests/{rid}")
        if out["status"] != "pending":
            assert out["status"] == "done", out
            return out
        assert time.monotonic() < deadline, f"rid {rid} stuck pending"
        time.sleep(0.02)


def _prom_samples(text: str) -> dict:
    """Prometheus text -> {(name, sorted-label-tuple): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^(\w+)(?:\{(.*)\})? (.+)$", line)
        assert m, f"unparseable metrics line: {line!r}"
        labels = tuple(sorted(
            tuple(kv.split("=", 1)) for kv in
            (m.group(2).replace('"', "").split(",") if m.group(2) else [])))
        out[(m.group(1), labels)] = float(m.group(3))
    return out


def _percentiles(lat_ms):
    return {"n": len(lat_ms),
            "p50_ms": float(np.percentile(lat_ms, 50)) if lat_ms else 0.0,
            "p99_ms": float(np.percentile(lat_ms, 99)) if lat_ms else 0.0}


def _burst(base: str, names, priorities, rounds: int, requests: int,
           prompt_len: int, seed0: int) -> dict:
    """Round-robin priority burst over ``names`` via /v1/submit; returns
    per-class scheduler latencies (ms) keyed ``hi``/``lo``."""
    hi = max(priorities)
    rids, label_of = [], {}
    for round_i in range(rounds):
        for j, name in enumerate(names):
            prio = priorities[(round_i * len(names) + j) % len(priorities)]
            resp = _http(base, "/v1/submit",
                         {"model": name, "requests": requests,
                          "prompt_len": prompt_len,
                          "seed": seed0 + round_i * len(names) + j,
                          "priority": prio})
            rids.append(resp["rid"])
            label_of[resp["rid"]] = "hi" if prio == hi else "lo"
    classes = {"hi": [], "lo": []}
    for rid in rids:
        out = _poll_done(base, rid)
        classes[label_of[rid]].append(out["latency_s"] * 1e3)
    return {"submitted": len(rids),
            "classes": {k: _percentiles(v) for k, v in classes.items()}}


def run(rounds: int, requests: int) -> dict:
    # the edge-tpu profile describes the device class; the fleet scenario
    # tightens the envelope via the CLI layer (defaults -> profile -> CLI,
    # the operator override path) so that with the third tenant aboard the
    # models' summed size EXCEEDS the usable pool — Eq. 1 short-circuits to
    # "give everyone its full size" when everything fits, and the replan
    # phase needs the contended regime where urgency actually moves budgets
    cfg = resolve_config(profile=PROFILE, env={},
                         cli={"workload": {"rounds": rounds,
                                           "requests": requests},
                              "runtime": {"budget_mb": 16.0}})
    priorities = [float(p) for p in cfg.workload.priorities]
    budget = int(cfg.runtime.budget_mb * 1e6)
    report = {"profile": PROFILE, "budget_mb": cfg.runtime.budget_mb,
              "executors": cfg.runtime.executors,
              "workload": {"rounds": rounds, "requests": requests,
                           "prompt_len": cfg.workload.prompt_len,
                           "priorities": priorities}}

    with tempfile.TemporaryDirectory() as d:
        names, rt, refs = build_runtime(cfg, d)
        for name, batch in _make_batches(cfg, refs).items():
            rt.forward(name, batch)             # warm: jit compile per block
        sched = ServingScheduler.from_config(rt, cfg)
        metrics = MetricsRegistry(rt, sched)
        with ControlPlane(rt, sched, metrics, port=0,
                          plan_shape=(cfg.workload.requests,
                                      cfg.workload.prompt_len),
                          reduce=cfg.reduce, workdir=d) as cp:
            base = cp.url
            health = _http(base, "/healthz")
            assert health["status"] == "ok", health

            # -- phase 1: burst against the resident tenants --------------
            report["burst"] = _burst(base, names, priorities, rounds,
                                     requests, cfg.workload.prompt_len,
                                     seed0=0)

            # -- phase 2: runtime model arrival + cold start --------------
            t0 = time.perf_counter()
            added = _http(base, "/v1/models",
                          {"arch": ARRIVAL_ARCH, "reduce": cfg.reduce})
            arrival_ms = (time.perf_counter() - t0) * 1e3
            assert added["added"] == ARRIVAL_ARCH, added
            listing = _http(base, "/v1/models")["models"]
            assert set(listing) == set(names) | {ARRIVAL_ARCH}, listing
            assert all(m["up"] for m in listing.values()), listing

            def one_request(seed: int) -> float:
                rid = _http(base, "/v1/submit",
                            {"model": ARRIVAL_ARCH, "requests": requests,
                             "prompt_len": cfg.workload.prompt_len,
                             "seed": seed, "priority": max(priorities)})["rid"]
                return _poll_done(base, rid)["latency_s"] * 1e3

            cold_ms = one_request(seed=100)     # jit compile + first swap-in
            warm_ms = [one_request(seed=101 + i) for i in range(3)]
            report["arrival"] = {
                "arch": ARRIVAL_ARCH,
                "register_ms": arrival_ms,      # build + add_model + replan
                "n_blocks": added["n_blocks"],
                "cold_first_request_ms": cold_ms,
                "warm_request_ms": _percentiles(warm_ms),
                "cold_over_warm": cold_ms / max(np.median(warm_ms), 1e-9),
            }

            # -- phase 3: post-arrival burst over ALL tenants -------------
            report["burst_post_arrival"] = _burst(
                base, names + [ARRIVAL_ARCH], priorities, rounds, requests,
                cfg.workload.prompt_len, seed0=200)

            # -- phase 4: live replan favouring the newcomer --------------
            # urgency responsiveness, size-independent: the newcomer's
            # budget under a 4x-urgency mix must exceed its budget under a
            # uniform mix (needs the contended regime — see the envelope
            # override above — else Eq. 1 never consults urgency at all)
            uniform = _http(base, "/v1/replan",
                            {"urgencies": {n: 1.0
                                           for n in names + [ARRIVAL_ARCH]}})
            urgencies = {name: 1.0 for name in names}
            urgencies[ARRIVAL_ARCH] = 4.0
            favored = _http(base, "/v1/replan", {"urgencies": urgencies})
            report["replan"] = {"uniform": uniform, "favored": favored}
            assert (favored["budgets_mb"][ARRIVAL_ARCH]
                    > uniform["budgets_mb"][ARRIVAL_ARCH]), \
                f"urgency-weighted replan ignored the mix: " \
                f"{uniform} vs {favored}"

            # -- phase 5: /metrics must agree with what the driver saw ----
            text = _http(base, "/metrics")
            samples = _prom_samples(text)
            families = {name for name, _ in samples}
            missing = [f for f in REQUIRED_FAMILIES if f not in families]
            assert not missing, f"scrape missing families: {missing}"
            completed = sum(v for (name, _), v in samples.items()
                            if name == "swapnet_requests_completed_total")
            expected = (report["burst"]["submitted"] + 4
                        + report["burst_post_arrival"]["submitted"])
            assert completed == expected, (completed, expected)
            peak = samples[("swapnet_ledger_peak_bytes", ())]
            assert peak <= budget, f"scrape shows budget breach: {peak}"
            report["scrape"] = {
                "families": len(families),
                "samples": len(samples),
                "bytes": len(text.encode()),
                "completed_total": completed,
                "peak_resident_mb": peak / 1e6,
                "cache_hit_rate": samples[("swapnet_cache_hit_rate", ())],
            }

            # -- phase 6: graceful shutdown -------------------------------
            assert _http(base, "/v1/shutdown", {})["shutting_down"]
            assert cp.shutdown_requested.wait(timeout=5)
        sched.shutdown()
        st = rt.stats()
        rt.close()
        resident_after_close = float(rt.ledger.resident)

    report["peak_resident_mb"] = st["peak_resident_mb"]
    report["budget_ok"] = bool(st["peak_resident_mb"] * 1e6 <= budget)
    report["ledger_clean"] = resident_after_close == 0.0
    report["clean_shutdown"] = True
    assert report["budget_ok"], report
    assert report["ledger_clean"], st
    return report


def write_report(report: dict, path: str = None) -> str:
    path = path or os.path.join(RESULTS_DIR, "BENCH_fleet.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small workload: the cheap CI data point")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="prompts per submitted batch")
    args = ap.parse_args()
    rounds = args.rounds if args.rounds is not None else (
        2 if args.smoke else 4)
    requests = args.requests if args.requests is not None else 2

    report = run(rounds, requests)
    for phase in ("burst", "burst_post_arrival"):
        for cls in ("hi", "lo"):
            c = report[phase]["classes"][cls]
            emit(f"fleet.{phase}.{cls}", c["p99_ms"] * 1e3,
                 f"n={c['n']};p50_ms={c['p50_ms']:.1f};"
                 f"p99_ms={c['p99_ms']:.1f}")
    arr = report["arrival"]
    emit("fleet.arrival", arr["register_ms"] * 1e3,
         f"arch={arr['arch']};register_ms={arr['register_ms']:.0f};"
         f"cold_ms={arr['cold_first_request_ms']:.1f};"
         f"warm_p50_ms={arr['warm_request_ms']['p50_ms']:.1f};"
         f"cold_over_warm={arr['cold_over_warm']:.2f}x")
    sc = report["scrape"]
    emit("fleet.scrape", 0.0,
         f"families={sc['families']};samples={sc['samples']};"
         f"completed={sc['completed_total']:.0f};"
         f"peak_mb={sc['peak_resident_mb']:.1f};"
         f"hit_rate={sc['cache_hit_rate']:.3f};"
         f"budget_ok={report['budget_ok']};"
         f"ledger_clean={report['ledger_clean']}")
    path = write_report(report)
    print(f"# fleet point -> {path}", flush=True)


if __name__ == "__main__":
    main()

"""A cell of the benchmark cut to a size the CPU runs in seconds: the
program's own ``.reduced()`` architecture (with the file's ``program`` cut
on top), the mix's shape with shorter prompts and outputs, and a budget
the tiny store fits. Of the configuration's ``model`` block only the keys
that ``.reduced()`` changes are restated; the rest stay as the file states
them, so ``check_model`` still holds them to the program."""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run                       # noqa: E402

WORKLOADS = {"chat": "qwen2.5-3b.chat",
             "danube": "h2o-danube-3-4b.chat"}
# the prefill mix has no cell in BENCHMARK.json: its kind is driven on
# the qwen configuration with the readers its metrics would have
PREFILL_METRICS = [("setup_s", "s", False), ("prefill_ms", "ms", False),
                   ("swap_wait_share.prefill", "%", True),
                   ("loader_gbps.prefill", "GB/s", True),
                   ("mfu.prefill", "%", True),
                   ("device_idle.prefill", "%", True)]
LIMITS = {"generate": {"served_gap": 0.05}, "prefill": {"logit_err": 0.05}}
PEAK = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def reduce_archs(monkeypatch) -> None:
    import repro.configs as rc
    orig = rc.get_arch
    monkeypatch.setattr(rc, "get_arch", lambda a: orig(a).reduced())


def cell(kind: str) -> "run.Cell":
    """``poisson`` is the chat mix sent on an open-loop schedule;
    ``prefill`` the ``prefill_long`` mix on the chat cell's model."""
    c = run.load_cell(WORKLOADS.get(kind, WORKLOADS["chat"]))
    if kind == "prefill":
        c.name = "qwen2.5-3b.prefill_long"
        c.mix = json.loads((BENCH / "traffic" / "prefill_long.json")
                           .read_text())
        c.metrics = [{"name": n, "unit": u, "per_layer": layer}
                     for n, u, layer in PREFILL_METRICS]
    if kind == "poisson":
        c.mix.update(arrivals={"process": "poisson", "rate": 20.0},
                     ramp_tokens=0)
    return shrink(c)


def shrink(c: "run.Cell") -> "run.Cell":
    """The cell at the size of the program's reduced architecture (under
    ``reduce_archs``), with the tiny mix, budget and limits. A ``model``
    key is restated where the reduced program's value differs from the
    registered one's, each with the file's cut applied."""
    import family
    import serving
    from repro.configs import ARCHS
    mc = serving.program_config(c.cfg, c.family)
    full = family.replace(ARCHS[c.cfg["arch"]], family.cut(c.cfg, c.family))
    for key, attr in c.family.MODEL_FIELDS.items():
        got = family.program_value(mc, attr)
        if got != family.program_value(full, attr):
            c.cfg["model"][key] = got
    c.cfg["budget_ratio"] = 1.0
    c.cfg["limits"] = LIMITS
    if c.mix["requests"] == "generate":
        c.mix.update(prompt={"dist": "lognormal", "median": 32, "sigma": 0.7,
                             "min": 16, "max": 64, "round_up": 16},
                     output={"dist": "lognormal", "median": 6, "sigma": 0.6,
                             "min": 2, "max": 12})
    else:
        c.mix["prompt"] = {"dist": "cycle", "values": [64, 128, 128, 256]}
    return c


def drive(kind: str, seed: int = 2**31 + 5, hooks=None, control=False,
          trace=False) -> dict:
    return run.run_cell(cell(kind), seed, 3.0, trace, PEAK, hooks=hooks,
                        control=control, log=lambda m: None)

"""The program's spans and counters as the harness reads them, on the CPU
at a tiny size: a traced chat run reports the paged-KV and admission
metrics, and its kept trace names idle gaps by program spans
(``bench/program_spans.py``). Run by hand:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import json

import pytest

import tiny

import program_spans as ps
import run

NEW = ("kv_upload_mb.chat", "kv_host_share.chat", "admit_wait_ms.chat")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    tiny.reduce_archs(mp)
    d = str(tmp_path_factory.mktemp("trace"))
    res = run.run_cell(tiny.cell("chat"), 2**31 + 5, 3.0, True, tiny.PEAK,
                       trace_dir=d, log=lambda m: None)
    mp.undo()
    return res, d


def test_traced_run_reports_the_program_metrics(traced):
    res, _ = traced
    assert res["correct"], res["checks"]
    for name in NEW:
        assert name in res["metrics"], name
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["kv_upload_mb.chat"] > 0
    assert 0 < m["kv_host_share.chat"] < 100
    assert m["admit_wait_ms.chat"] >= 0


def test_idle_gaps_carry_program_spans(traced, capsys):
    res, d = traced
    trace, spans = ps.load(d)
    seconds = res["device_busy"]["window_s"]
    # the bench.window span opens when its annotation is made, before the
    # load's ramp: the window is the ``seconds`` that end where it ends
    (lo, hi), = trace.spans("bench.window")
    assert hi - lo > seconds
    assert ps.main([d, "--seconds", str(seconds)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["window_s"] == pytest.approx(seconds)
    for name, t in out["executor_s_loader_busy"].items():
        assert 0 <= t <= out["executor_s"][name] + 1e-9
    assert out["loader_s"].keys() <= {"swap.read", "swap.unpack",
                                      "swap.dispatch"}
    names = {s.name for s in spans}
    assert {"decode_step", "admit", "exec", "exec.sync", "kv.readback.wait",
            "kv.readback", "kv.upload", "swap.read", "swap.dispatch"} <= names
    labels = [lab for lab, _ in out["idle_gaps"]]
    assert any(lab.split(">")[0] in ps.ROOTS for lab in labels), labels
    assert 0 < out["kv_host_share"] < 100
    assert out["executor_s"]


def test_spans_without_a_window_or_program():
    """A trace of a program without spans (the parent of this change)
    labels gaps as before and finds nothing to sum."""
    assert ps.chain([], 1.0) == []
    assert ps.label([], [("bench.decode_step", 0.0, 2.0)], 1.0) \
        == "decode_step"
    assert ps.label([], [], 1.0) == "outside_layers"
    assert ps.segments([], ("/host:CPU", 0), 0.0, 1.0) == []

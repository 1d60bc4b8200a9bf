"""Program spans and counters of the decode path.

The spans (``repro.tracing.span``) land in a ``jax.profiler`` trace nested
in the span that caused them, on the thread that ran them, and nowhere
when no profiler session is open. The counters (``PagedKVCache``'s rows
appended on the device, ``BatchDecodeEngine``'s admission wait) count each
row and each admission once, and reach the operator's ``/metrics`` surface.
"""
import dataclasses
import glob
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.cost_model import DelayModel
from repro.core.multi_model import MultiModelRuntime
from repro.core.runtime import SwappedModel
from repro.core.serving_scheduler import ServingScheduler
from repro.core.swap_engine import MemoryLedger, SwapStats
from repro.models.transformer import Model
from repro.serving.batch_engine import BatchDecodeEngine
from repro.serving.engine import Request
from repro.serving.metrics import METRIC_FAMILIES, MetricsRegistry
from repro.serving.paged_kv import PagedKVCache

MB = 1024 * 1024


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(ARCHS["qwen2.5-3b"].reduced(), dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(4)]
    return cfg, model, params, prompts


def _engine(model, params, workdir, *, max_pages=8, max_batch=2):
    sm = SwappedModel(model, params, workdir, mode="snet")
    sm.partition(budget=8 * MB, dm=DelayModel(), batch=2, seq=16)
    kv = PagedKVCache(model.cfg, MemoryLedger(1 << 30), page_tokens=4,
                      max_pages=max_pages)
    return sm, BatchDecodeEngine(sm, kv, max_batch=max_batch)


def _events(trace_dir):
    """``swapnet.*`` host events of the newest trace under ``trace_dir``:
    (thread, name, start_ns, end_ns, args), the thread being the event's
    line in the trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("swapnet."):
                    out.append(((plane.name, i), ev.name[len("swapnet."):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _inside(inner, outer):
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """Three generate requests through the scheduler, two batch slots,
    under a profiler session: admissions, decode steps, loader reads."""
    cfg, model, params, prompts = setup
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(budget=24 * MB, cache_frac=0.2, kv_frac=0.25,
                               page_tokens=4, max_batch=2)
        rt.add_model("m", model, params, d)
        rt.plan(batch=2, seq=16)
        reqs = [Request(i, list(prompts[i]), max_new_tokens=n)
                for i, n in enumerate([3, 5, 4])]
        jax.profiler.start_trace(trace_dir)
        with ServingScheduler(rt, executors=1) as sched:
            for h in [sched.submit_generate("m", r) for r in reqs]:
                h.wait(timeout=600)
        jax.profiler.stop_trace()
        be = rt.batch_engine("m")
        samples = MetricsRegistry(rt, sched).collect()
        rt.close()
    return _events(trace_dir), be, samples


def test_decode_step_spans_nest_on_the_executor_thread(traced):
    events, be, _ = traced
    by = {}
    for ev in events:
        by.setdefault(ev[1], []).append(ev)
    steps = by["decode_step"]
    assert len(steps) == int(be.stats()["decode_steps"])
    assert sorted(s[4]["step"] for s in steps) == sorted(
        t.step for t in be.trace if t.batch)
    # decode_step > exec > kv.write, and exec > exec.sync, on one thread;
    # the decode path's appends run inside a block's exec, the prompt's
    # inside its admission. K/V stay on the device: no readback, no wait
    # for the K/V's programs, no pool upload
    for name in ("kv.readback.wait", "kv.readback", "kv.upload"):
        assert name not in by, name
    for ev in by["kv.write"]:
        assert any(_inside(ev, e) for e in by["exec"] + by["admit"])
        assert len([s for s in steps + by["admit"] if _inside(ev, s)]) == 1
    for ev in by["exec.sync"]:
        assert any(_inside(ev, e) for e in by["exec"])
    assert all(any(_inside(e, s) for s in steps + by["prefill"])
               for e in by["exec"])
    appends = [e for e in by["kv.write"] if any(_inside(e, s) for s in steps)]
    assert len(appends) == len(steps) * be.sm.cfg.n_layers


def test_admission_spans_carry_the_request(traced):
    events, be, _ = traced
    admits = [e for e in events if e[1] == "admit"]
    assert sorted(e[4]["rid"] for e in admits) == [0, 1, 2]
    prefills = [e for e in events if e[1] == "prefill"]
    assert all(any(_inside(p, a) for a in admits) for p in prefills)
    # the prompt's K/V are seeded inside the admission, one append a layer
    for a in admits:
        seeded = [e for e in events if e[1] == "kv.write" and _inside(e, a)]
        assert len(seeded) == be.sm.cfg.n_layers


def test_loader_stages_run_on_their_own_thread(traced):
    events, _, _ = traced
    executor = {e[0] for e in events if e[1] == "decode_step"}
    assert len(executor) == 1
    for stage in ("swap.read", "swap.unpack", "swap.dispatch"):
        evs = [e for e in events if e[1] == stage]
        assert evs, stage
        assert all("unit" in e[4] for e in evs)
        assert {e[0] for e in evs}.isdisjoint(executor), stage
    waits = [e for e in events if e[1] == "swap.wait"]
    assert waits and {e[0] for e in waits} == executor


def test_no_span_recorded_without_a_profiler_session(setup, tmp_path):
    cfg, model, params, prompts = setup
    with tempfile.TemporaryDirectory() as d:
        sm, be = _engine(model, params, d)
        be.submit(Request(0, list(prompts[0]), max_new_tokens=3))
        be.run_all()               # spans opened with no session
        jax.profiler.start_trace(str(tmp_path))
        jax.profiler.stop_trace()
        sm.close()
    assert be.stats()["decode_steps"] >= 2
    assert _events(str(tmp_path)) == []


def test_kv_upload_bytes_per_decode_step(setup):
    """No decode step uploads a pool byte: the pools live on the device
    and take each step's rows in place. What grows is ``rows_written``,
    by n_layers x the batch a step. The engine's ``stats()`` pairs the
    counters with the steps that ended, so a reading taken inside a step
    still divides to the exact count."""
    cfg, model, params, prompts = setup
    with tempfile.TemporaryDirectory() as d:
        sm, be = _engine(model, params, d)
        kv = be.kv
        pool = kv.k_pools[0].nbytes
        for i, n in enumerate([3, 5]):
            be.submit(Request(i, list(prompts[i]), max_new_tokens=n))
        step = sm.decode_step_paged
        inside = []

        def reading_inside(batch, view):
            out = step(batch, view)
            inside.append(be.stats())          # the step's appends are done
            return out
        sm.decode_step_paged = reading_inside
        seen = 0
        while True:
            rows = kv.rows_written
            before = be.stats()
            tr = be.step()
            if tr is None:
                break
            assert kv.upload_bytes == 0
            seeded = cfg.n_layers * sum(len(prompts[r]) for r in tr.admitted)
            if not tr.batch:
                assert kv.rows_written - rows == seeded
                continue
            seen += 1
            step_rows = cfg.n_layers * len(tr.batch)
            assert kv.rows_written - rows == seeded + step_rows
            assert inside[-1]["kv_rows_written"] == before["kv_rows_written"]
            assert inside[-1]["decode_steps"] == before["decode_steps"]
            after = be.stats()
            assert after["kv_rows_written"] - before["kv_rows_written"] \
                == seeded + step_rows
            assert after["kv_upload_bytes"] == 0
        sm.close()
    assert seen >= 4
    assert kv.device_pool_bytes == cfg.n_layers * 2 * pool
    assert kv.host_s > 0
    st = be.stats()
    assert st["kv_upload_bytes"] == kv.upload_bytes == 0
    assert st["kv_rows_written"] == kv.rows_written
    assert st["kv_device_pool_bytes"] == cfg.n_layers * 2 * pool


@pytest.mark.parametrize("scenario", ["preempted", "pool_full"])
def test_each_admission_counted_once(setup, scenario):
    """``preempted``: page pressure evicts the low-priority sequence, which
    is re-queued and admitted again. ``pool_full``: the second request
    cannot get its pages until the first retires, and goes back to the
    queue's head in between. Either way ``admitted`` counts admissions,
    not pops, and each wait ends at its admission."""
    cfg, model, params, prompts = setup
    max_pages = 5 if scenario == "preempted" else 3
    with tempfile.TemporaryDirectory() as d:
        sm, be = _engine(model, params, d, max_pages=max_pages)
        be.submit(Request(0, list(prompts[0]),
                          max_new_tokens=5 if scenario == "preempted" else 4,
                          priority=2.0))
        be.submit(Request(1, list(prompts[1]), max_new_tokens=4,
                          priority=1.0))
        be.run_all()
        sm.close()
    admissions = sum(len(t.admitted) for t in be.trace)
    if scenario == "preempted":
        assert be.preemptions >= 1 and admissions == 3
    else:
        assert be.preemptions == 0 and admissions == 2
        retired = next(t.step for t in be.trace if 0 in t.retired)
        assert next(t.step for t in be.trace if 1 in t.admitted) > retired
    st = be.stats()
    assert st["admitted"] == admissions
    assert st["admit_wait_s"] > 0
    assert be._queued_at == {}


def test_registry_exports_the_new_counters(traced):
    _, be, samples = traced
    got = {name: v for name, labels, v in samples
           if labels == {"model": "m"}}
    assert got["swapnet_kv_rows_written_total"] == be.kv.rows_written > 0
    assert got["swapnet_kv_upload_bytes_total"] == be.kv.upload_bytes == 0
    assert got["swapnet_kv_device_pool_bytes"] == be.kv.device_pool_bytes > 0
    assert got["swapnet_admit_wait_seconds_total"] == be.admit_wait_s
    assert got["swapnet_admitted_total"] == be.admitted == 3
    families = {name for name, _, _ in METRIC_FAMILIES}
    assert {"swapnet_kv_rows_written_total",
            "swapnet_kv_upload_bytes_total", "swapnet_kv_device_pool_bytes",
            "swapnet_admit_wait_seconds_total",
            "swapnet_admitted_total"} <= families


def test_swap_history_stays_bounded(setup, monkeypatch):
    """A long-lived engine keeps the last ``HISTORY`` timeline events and
    per-event times, the newest ones, while its byte totals keep
    counting."""
    cfg, model, params, prompts = setup
    monkeypatch.setattr(SwapStats, "HISTORY", 40)
    batch = {"tokens": jax.numpy.asarray([prompts[0]], jax.numpy.int32)}
    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d, mode="snet")
        sm.partition(budget=8 * MB, dm=DelayModel(), batch=1, seq=8)
        _, first = sm.forward(batch)
        for _ in range(5):
            _, stats = sm.forward(batch)
        st = sm.engine.stats
        sm.close()
    assert len(st.timeline) == 40
    for ring in (st.t_in, st.t_ex, st.t_out, st.t_wait):
        assert 0 < len(ring) <= 40
    assert len(stats["t_in"]) <= 40
    assert st.timeline[-1][0] == "exec"
    assert stats["bytes_swapped"] == 6 * first["bytes_swapped"]
    assert 0.0 <= st.overlap_efficiency() <= 1.0
    full = SwapStats()
    full.timeline.extend(("exec", float(i), float(i)) for i in range(50))
    assert [e[1] for e in full.timeline] == [float(i) for i in range(10, 50)]

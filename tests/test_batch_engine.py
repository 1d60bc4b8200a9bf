"""Continuous-batching decode engine (serving tentpole).

Greedy decode is deterministic, so batching/paging/preemption must be
INVISIBLE in the outputs: every request's tokens must equal what a solo
contiguous-cache run produces, while the step trace shows batch membership
actually changing every iteration (admissions and retirements at step
boundaries, preemption-by-recomputation under page pressure).
"""
import contextlib
import dataclasses
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.cost_model import DelayModel
from repro.core.multi_model import MultiModelRuntime
from repro.core.runtime import SwappedModel
from repro.core.serving_scheduler import ServingScheduler
from repro.core.swap_engine import MemoryLedger
from repro.models.transformer import Model
from repro.serving.batch_engine import BatchDecodeEngine
from repro.serving.engine import Request, ServingEngine
from repro.serving.paged_kv import PagedKVCache

MB = 1024 * 1024


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(ARCHS["qwen2.5-3b"].reduced(), dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(5)]
    eng = ServingEngine(model, params, max_len=64)

    def solo(prompt, max_new):
        r = Request(0, list(prompt), max_new_tokens=max_new)
        eng.generate([r])
        return list(r.output)
    return cfg, model, params, prompts, solo


def _swapped(model, params, workdir):
    sm = SwappedModel(model, params, workdir, mode="snet")
    sm.partition(budget=8 * MB, dm=DelayModel(), batch=2, seq=16)
    return sm


def test_continuous_batching_exact_with_step_trace(setup):
    cfg, model, params, prompts, solo = setup
    max_new = [2, 6, 3, 5, 4]
    want = [solo(prompts[i], max_new[i]) for i in range(5)]
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(1 << 30), page_tokens=4,
                          max_pages=8)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        reqs = [Request(i, list(prompts[i]), max_new_tokens=max_new[i])
                for i in range(5)]
        for r in reqs:
            be.submit(r)
        be.run_all()
        sm.close()
    assert [list(r.output) for r in reqs] == want

    # ---- the trace is a real continuous-batching log
    tr = be.trace
    assert sorted(r for t in tr for r in t.retired) == [0, 1, 2, 3, 4]
    assert sorted(r for t in tr for r in t.admitted) == [0, 1, 2, 3, 4]
    assert all(len(t.batch) <= 2 for t in tr)
    # each request retires at ITS OWN length: rid 0 (2 tokens) leaves long
    # before rid 1 (6 tokens), and its slot is refilled mid-flight — some
    # step admits a new sequence while another is still decoding
    retire_step = {r: t.step for t in tr for r in t.retired}
    assert retire_step[0] < retire_step[1]
    refills = [t for t in tr if t.admitted and t.batch]
    assert refills, "no admission ever joined a running batch"
    # admissions happened at 3+ distinct step boundaries (5 reqs, 2 slots)
    assert len({t.step for t in tr if t.admitted}) >= 3
    # pages were freed mid-run: pool occupancy is not monotone
    pages = [t.kv_pages for t in tr]
    assert any(b < a for a, b in zip(pages, pages[1:]))
    assert kv.pages_in_use == 0
    st = be.stats()
    assert st["tokens_emitted"] == sum(max_new)
    assert 0 < st["mean_occupancy"] <= 1.0


def test_preemption_by_recomputation_exact(setup):
    """Page pressure evicts the lowest-priority sequence mid-decode; it is
    re-admitted (prompt + emitted output recomputed) and still produces
    exactly the solo outputs."""
    cfg, model, params, prompts, solo = setup
    want_hi = solo(prompts[0], 5)
    want_lo = solo(prompts[1], 4)
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        # prompts are 8 tokens = 2 pages of 4; 5 pages total, so two admitted
        # sequences leave ONE spare page: the first boundary crossing evicts
        kv = PagedKVCache(cfg, MemoryLedger(1 << 30), page_tokens=4,
                          max_pages=5)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        hi = Request(0, list(prompts[0]), max_new_tokens=5, priority=2.0)
        lo = Request(1, list(prompts[1]), max_new_tokens=4, priority=1.0)
        be.submit(hi)
        be.submit(lo)
        be.run_all()
        sm.close()
    assert list(hi.output) == want_hi
    assert list(lo.output) == want_lo
    assert be.preemptions >= 1
    preempted = [r for t in be.trace for r in t.preempted]
    assert 1 in preempted and 0 not in preempted, \
        "eviction must pick the LOW priority sequence"
    # rid 1 was admitted twice (initial + recompute)
    assert sum(t.admitted.count(1) for t in be.trace) == 2
    # the high-priority sequence was never stalled: it decoded every step
    # from its admission to its retirement
    hi_steps = [t.step for t in be.trace if 0 in t.batch or 0 in t.retired]
    assert hi_steps == list(range(min(hi_steps), max(hi_steps) + 1))


def test_eos_retires_early(setup):
    cfg, model, params, prompts, solo = setup
    # find a generation with a token whose FIRST occurrence is mid-sequence,
    # so stopping on it as EOS genuinely exercises early retirement
    full = eos_at = None
    for p in prompts:
        full = solo(p, 6)
        ks = [k for k in range(1, len(full)) if full[k] not in full[:k]]
        if ks:
            prompt, eos_at = p, ks[0]
            break
    assert eos_at is not None, "all sample generations are constant"
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(1 << 30), page_tokens=4,
                          max_pages=8)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        r = Request(0, list(prompt), max_new_tokens=6, eos=full[eos_at])
        be.submit(r)
        be.run_all()
        sm.close()
    assert list(r.output) == full[:eos_at + 1]


def test_oversized_prompt_raises(setup):
    cfg, model, params, prompts, _ = setup
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(1 << 30), page_tokens=4,
                          max_pages=1)       # 4-token capacity, 8-token prompt
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        be.submit(Request(0, list(prompts[0]), max_new_tokens=2))
        with pytest.raises(MemoryError):
            be.run_all()
        sm.close()


def test_scheduler_generate_integration(setup):
    """submit_generate drives decode through the shared-budget runtime: one
    driver's stepping serves other drivers' sequences, completion comes from
    the retire callback, and the KV pool + ledger end clean."""
    cfg, model, params, prompts, solo = setup
    max_new = [3, 5, 4]
    want = [solo(prompts[i], max_new[i]) for i in range(3)]
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(budget=24 * MB, cache_frac=0.2, kv_frac=0.25,
                               page_tokens=4, max_batch=2)
        rt.add_model("m", model, params, d)
        rt.plan(batch=2, seq=16)
        reqs = [Request(i, list(prompts[i]), max_new_tokens=max_new[i])
                for i in range(3)]
        with ServingScheduler(rt, executors=1) as sched:
            handles = [sched.submit_generate("m", r) for r in reqs]
            for h in handles:
                h.wait(timeout=600)
        assert [list(r.output) for r in reqs] == want
        be = rt.batch_engine("m")
        assert be.kv.pages_in_use == 0
        assert len(sched.completed) == 3
        assert all(h.latency_s > 0 for h in handles)
        rt.close()


def test_decode_keeps_kv_on_the_device(setup, monkeypatch):
    """K/V never leave the device. Past admission, every decode step
    writes each layer's pools in place: the same device buffers before and
    after the step (the append donates them), no pool byte uploaded, and
    no span but ``kv.write`` in the KV path (no readback, no upload).
    ``rows_written`` counts the prompt tokens seeded plus the tokens
    decoded, times the layers; the tokens stay the solo run's."""
    import repro.core.runtime as runtime_mod
    import repro.serving.batch_engine as engine_mod
    import repro.serving.paged_kv as paged_kv_mod
    cfg, model, params, prompts, solo = setup
    max_new = [4, 3]
    want = [solo(prompts[i], max_new[i]) for i in range(2)]
    opened = []

    def recording(name, **_):
        opened.append(name)
        return contextlib.nullcontext()
    for mod in (runtime_mod, engine_mod, paged_kv_mod):
        monkeypatch.setattr(mod, "span", recording)
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(1 << 30), page_tokens=4,
                          max_pages=8)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        reqs = [Request(i, list(prompts[i]), max_new_tokens=max_new[i])
                for i in range(2)]
        for r in reqs:
            be.submit(r)

        def buffers():
            return [(k.unsafe_buffer_pointer(), v.unsafe_buffer_pointer())
                    for k, v in zip(kv.k_pools, kv.v_pools)]
        decoded = seeded = 0
        while True:
            before, rows = buffers(), kv.rows_written
            del opened[:]
            tr = be.step()
            if tr is None:
                break
            seeded += sum(len(prompts[rid]) for rid in tr.admitted)
            decoded += len(tr.batch)
            assert buffers() == before
            assert kv.upload_bytes == 0
            assert kv.rows_written - rows == cfg.n_layers * (
                len(tr.batch) + sum(len(prompts[rid])
                                    for rid in tr.admitted))
            kv_spans = [n for n in opened if n.startswith("kv.")]
            assert set(kv_spans) <= {"kv.write"}
            if tr.batch and not tr.admitted:
                assert len(kv_spans) == cfg.n_layers
        sm.close()
    assert [list(r.output) for r in reqs] == want
    assert decoded == sum(max_new) - len(reqs)   # the first token: prefill
    assert kv.rows_written == cfg.n_layers * (seeded + decoded)
    assert be.stats()["kv_rows_written"] == kv.rows_written

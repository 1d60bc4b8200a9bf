"""Concurrent priority-aware multi-tenant serving (ISSUE 5).

Covers the tentpole's acceptance invariants:
  * bit-identity under concurrency AND under block-boundary preemption
    (a preempted+resumed pass re-executes nothing);
  * single-charge of shared blocks with concurrent executors;
  * the shared ledger never exceeds the budget under adversarial
    interleavings (fuzzed reserve/add/drop and real concurrent serving);
  * priority wakeup on the blocking ``reserve()``;
  * the priority-inversion regression: a high-urgency arrival is served
    before earlier low-priority queue entries instead of draining behind
    them;
  * ``MultiModelRuntime`` planning edges: ``block_budget() <= 0`` raises,
    ``cache_frac=0.0`` serves correctly with no cache;
  * ``replan_budgets`` reacting to the live urgency mix.
"""
import dataclasses
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.core.cost_model import DelayModel
from repro.core.multi_model import MultiModelRuntime
from repro.core.runtime import SwappedModel
from repro.core.serving_scheduler import RequestQueue, ServingRequest, \
    ServingScheduler
from repro.core.swap_engine import MemoryLedger
from repro.models.transformer import Model

from conftest import make_batch


def _setup(arch, seed=0):
    cfg = dataclasses.replace(ARCHS[arch].reduced(), dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    batch = make_batch(cfg, ShapeConfig("p", 32, 2, "prefill"))
    return cfg, model, params, batch


# ----------------------------------------------------------------- ledger
def test_reserve_blocks_until_bytes_free():
    led = MemoryLedger(100)
    led.add("a", 80)
    admitted = []

    def waiter():
        led.reserve("b", 50, priority=1.0)
        admitted.append("b")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert not admitted           # 80 + 50 > 100: must be waiting
    led.drop("a")
    t.join(timeout=5)
    assert admitted == ["b"]
    assert led.resident == 50
    assert led.peak <= 100


def test_reserve_priority_wakeup_order():
    """When bytes free, the HIGHEST-priority waiter is admitted first,
    regardless of wait order; FIFO within one priority class."""
    led = MemoryLedger(100)
    led.add("filler", 100)
    order = []
    started = []

    def waiter(name, prio):
        started.append(name)
        led.reserve(name, 60, priority=prio)
        order.append(name)
        time.sleep(0.05)          # hold so admissions serialize observably
        led.drop(name)

    threads = []
    for name, prio in (("lo", 1.0), ("mid", 2.0), ("hi", 8.0)):
        t = threading.Thread(target=waiter, args=(name, prio))
        t.start()
        threads.append(t)
        time.sleep(0.05)          # deterministic wait order: lo, mid, hi
    assert started == ["lo", "mid", "hi"] and not order
    led.drop("filler")
    for t in threads:
        t.join(timeout=5)
    assert order == ["hi", "mid", "lo"]
    assert led.peak <= 100


def test_reserve_timeout_and_never_fits():
    led = MemoryLedger(100)
    with pytest.raises(MemoryError):
        led.reserve("huge", 101)          # can never fit: fail fast
    led.add("a", 90)
    t0 = time.perf_counter()
    with pytest.raises(MemoryError):
        led.reserve("b", 50, timeout=0.1)
    assert time.perf_counter() - t0 < 2.0
    assert led.resident == 90             # failed reserve charged nothing


def test_ledger_never_exceeds_budget_adversarial():
    """Fuzzed interleavings: many threads adding/reserving/dropping random
    sizes; the budget is an invariant, not an observation."""
    budget = 1000
    led = MemoryLedger(budget)
    rng_seed = 0

    def hammer(tid):
        rng = np.random.default_rng(tid + rng_seed)
        held = []
        for i in range(200):
            if held and rng.random() < 0.45:
                led.drop(held.pop())
            else:
                key = (tid, i)
                n = int(rng.integers(1, 400))
                if rng.random() < 0.5:
                    try:
                        led.add(key, n)
                        held.append(key)
                    except MemoryError:
                        pass
                else:
                    try:
                        led.reserve(key, n, priority=float(tid % 3),
                                    timeout=0.02)
                        held.append(key)
                    except MemoryError:
                        pass
        for key in held:
            led.drop(key)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert led.peak <= budget
    assert led.resident == 0


# ------------------------------------------------------------ request queue
def test_request_queue_urgency_weighted_deadline():
    q = RequestQueue(default_slack=1.0)
    now = time.perf_counter()
    lo = ServingRequest("a", {}, priority=1.0, rid=0, arrival=now)
    hi = ServingRequest("b", {}, priority=8.0, rid=1, arrival=now + 0.01)
    tight = ServingRequest("c", {}, priority=1.0, deadline=0.05, rid=2,
                           arrival=now + 0.02)
    for r in (lo, hi, tight):
        q.submit(r)
    assert q.max_waiting_priority() == 8.0
    assert q.urgency_mix() == {"a": 1.0, "b": 8.0, "c": 1.0}
    # explicit 50 ms deadline beats urgency-8's 1s/8 slack; both beat lo
    assert q.pop_ready().rid == 2
    assert q.pop_ready().rid == 1
    assert q.pop_ready().rid == 0


def test_request_queue_busy_model_filter():
    q = RequestQueue(default_slack=1.0)
    now = time.perf_counter()
    q.submit(ServingRequest("a", {}, priority=8.0, rid=0, arrival=now))
    q.submit(ServingRequest("b", {}, priority=1.0, rid=1, arrival=now))
    got = q.pop_ready(busy=("a",))
    assert got.rid == 1                   # urgent req's model is busy
    assert q.pop_ready(busy=("a",), timeout=0.01) is None
    assert q.pop_ready().rid == 0         # still queued, served once free


# ----------------------------------------------------- preemption / resume
def test_preempted_pass_resumes_bit_identical():
    """Yield at EVERY block boundary; the stitched pass must be
    byte-for-byte the uninterrupted pass, and each pause must leave only
    cache-resident bytes charged (prefetches drained)."""
    cfg, model, params, batch = _setup("qwen2.5-3b")
    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d, mode="snet")
        sm.partition(budget=8 * 1024 * 1024, dm=DelayModel(), batch=2, seq=32)
        assert sm.plan.n_blocks >= 2
        ref, _ = sm.forward(batch)
        state, stats = sm.forward_partial(batch,
                                          should_yield=lambda s: True)
        resumes = 0
        while stats is None:
            assert sm.engine.ledger.resident == \
                sm.engine.cache.resident_bytes
            resumes += 1
            state, stats = sm.forward_partial(batch, state=state,
                                              should_yield=lambda s: True)
        sm.close()
    assert resumes == sm.plan.n_blocks - 1
    assert stats["preemptions"] == resumes
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(state.logits))


def test_scheduler_concurrent_bit_identity_and_budget():
    """2 executors, mixed priorities, repeated requests: every response
    equals the unswapped reference, repeats are byte-stable, and the shared
    ledger never exceeded the budget."""
    budget = 24 * 1024 * 1024
    archs = ["qwen2.5-3b", "gemma2-9b"]
    setups = {a: _setup(a, seed=i) for i, a in enumerate(archs)}
    refs = {a: np.asarray(jax.jit(m.prefill)(p, b)[0][:, -1:])
            for a, (c, m, p, b) in setups.items()}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(budget, cache_frac=0.25, executors=2)
        for a, (cfg, model, params, _) in setups.items():
            rt.add_model(a, model, params, d)
        rt.plan(batch=2, seq=32)
        with ServingScheduler(rt) as sched:
            reqs = []
            for rnd in range(3):
                for a in archs:
                    prio = 8.0 if rnd == 1 else 1.0
                    reqs.append(sched.submit(a, setups[a][3], priority=prio))
            for r in reqs:
                r.wait(timeout=300)
        st = rt.stats()
        rt.close()
    assert st["peak_resident_mb"] * 1e6 <= budget
    assert rt.ledger.peak <= budget
    by_model = {}
    for r in reqs:
        got = np.asarray(r.logits)
        np.testing.assert_allclose(got, refs[r.model], rtol=1e-4, atol=1e-4)
        if r.model in by_model:              # repeats are byte-stable
            np.testing.assert_array_equal(got, by_model[r.model])
        by_model[r.model] = got
    assert len(sched.completed) == len(reqs)


def test_scheduler_shared_blocks_single_charge_concurrent():
    """zamba2's pinned shared block under CONCURRENT serving: after the
    queue drains, the only charged bytes are the cache's, and the shared
    unit was charged exactly once."""
    archs = ["zamba2-7b", "qwen2.5-3b"]
    setups = {a: _setup(a, seed=i) for i, a in enumerate(archs)}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(32 * 1024 * 1024, cache_frac=0.2, executors=2)
        for a, (cfg, model, params, _) in setups.items():
            rt.add_model(a, model, params, d)
        rt.plan(batch=2, seq=32)
        with ServingScheduler(rt) as sched:
            reqs = [sched.submit(a, setups[a][3],
                                 priority=float(1 + (i % 2) * 7))
                    for i in range(4) for a in archs]
            for r in reqs:
                r.wait(timeout=300)
        shared = rt.models["zamba2-7b"].store.nbytes("zamba2-7b/shared_attn")
        assert shared > 0
        # every in-flight handle dropped: only cache entries stay charged,
        # and the pinned shared unit is exactly one of them (single charge)
        assert rt.ledger.resident == rt.cache.resident_bytes
        assert rt.cache.resident_bytes >= shared
        rt.close()


def test_priority_inversion_regression():
    """One executor, a backlog of low-priority work, then a high-urgency
    arrival: it must complete BEFORE the queued low-priority requests
    (with preemption it overtakes the in-flight pass at a block boundary
    instead of waiting out the whole backlog)."""
    archs = ["qwen2.5-3b", "gemma2-9b"]
    setups = {a: _setup(a, seed=i) for i, a in enumerate(archs)}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(10 * 1024 * 1024, cache_frac=0.25,
                               executors=1)
        for a, (cfg, model, params, _) in setups.items():
            rt.add_model(a, model, params, d)
        rt.plan(batch=2, seq=32)
        for a in archs:
            rt.forward(a, setups[a][3])          # warm outside the clock
        sched = ServingScheduler(rt, executors=1, preempt=True)
        lo = [sched.submit("qwen2.5-3b", setups["qwen2.5-3b"][3],
                           priority=1.0) for _ in range(3)]
        # arrive while the first lo pass is in flight: wait for the executor
        # to take it off the queue (a warm pass lasts milliseconds, so a
        # fixed sleep can outlast several of them)
        t_end = time.monotonic() + 60
        while len(sched.queue) == len(lo) and time.monotonic() < t_end:
            time.sleep(0.0005)
        hi = sched.submit("gemma2-9b", setups["gemma2-9b"][3], priority=8.0)
        for r in lo + [hi]:
            r.wait(timeout=300)
        sched.shutdown()
        rt.close()
    done_at = {r.rid: i for i, r in enumerate(sched.completed)}
    # the hi request never drains behind the lo backlog: at most the
    # in-flight lo pass finishes ahead of it
    assert done_at[hi.rid] <= 1
    assert done_at[hi.rid] < done_at[lo[2].rid]


# ------------------------------------------------------- runtime planning
def test_plan_raises_when_no_block_budget():
    """cache + pinned >= budget must fail loudly at plan time."""
    cfg, model, params, batch = _setup("zamba2-7b")
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(512 * 1024, cache_frac=0.9)
        rt.add_model("z", model, params, d)
        # pinned shared block + 90% cache swallow the whole budget
        assert rt.block_budget() <= 0
        with pytest.raises(ValueError, match="no room for blocks"):
            rt.plan(batch=2, seq=32)
        rt.close()


def test_cache_frac_zero_degenerate_path():
    """cache_frac=0.0: a pin-only cache — serving stays lossless, nothing
    unpinned is ever cached, and the block budget is the full budget."""
    cfg, model, params, batch = _setup("qwen2.5-3b")
    ref = np.asarray(jax.jit(model.prefill)(params, batch)[0][:, -1:])
    budget = 12 * 1024 * 1024
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(budget, cache_frac=0.0)
        rt.add_model("q", model, params, d)
        rt.plan(batch=2, seq=32)
        assert rt.cache.capacity == 0
        assert rt.block_budget() == budget      # qwen pins nothing
        out1, _ = rt.forward("q", batch)
        out2, stats = rt.forward("q", batch)
        assert rt.cache.resident_bytes == 0     # nothing admitted
        assert stats["cache_hit_rate"] == 0.0
        rt.close()
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_allclose(np.asarray(out1), ref, rtol=1e-4, atol=1e-4)


def test_replan_budgets_follows_urgency_mix():
    """Same-size models: a skewed urgency mix must tilt the Eq. 1 split
    toward the urgent model (its budget strictly above the uniform share)
    while per-model budgets keep summing to the block budget."""
    archs = ["qwen2.5-3b", "gemma2-9b"]
    setups = {a: _setup(a, seed=i) for i, a in enumerate(archs)}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(10 * 1024 * 1024, cache_frac=0.25,
                               executors=2)
        for a, (cfg, model, params, _) in setups.items():
            rt.add_model(a, model, params, d)
        rt.plan(batch=2, seq=32)
        budgets = rt.replan_budgets({"qwen2.5-3b": 8.0, "gemma2-9b": 1.0})
        assert budgets["qwen2.5-3b"] > budgets["gemma2-9b"]
        assert sum(budgets.values()) <= rt.block_budget() + 1
        # runtime still serves correctly off the re-selected plans
        out, _ = rt.forward("qwen2.5-3b", setups["qwen2.5-3b"][3])
        rt.close()
    assert np.asarray(out).shape[0] == 2

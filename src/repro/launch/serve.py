"""Serving driver over the layered configuration system (``repro.config``).

Configuration resolves defaults -> device-class profile -> env
(``SWAPNET_*``) -> CLI, so a deployment is one flag instead of fifteen:

    PYTHONPATH=src python -m repro.launch.serve --profile edge-tpu
        # two tenants, 24 MB shared budget, 2 executors, priority classes
        # 1/8 with block-boundary preemption — end to end, zero other flags
    PYTHONPATH=src python -m repro.launch.serve --profile mcu
    PYTHONPATH=src python -m repro.launch.serve --profile workstation
    SWAPNET_RUNTIME_BUDGET_MB=48 python -m repro.launch.serve --profile edge-tpu
        # env layer overrides the profile; CLI flags override the env
    PYTHONPATH=src python -m repro.launch.serve --profile edge-tpu --http
        # same serving system behind the HTTP control plane
        # (submit/poll/cancel, /healthz, Prometheus /metrics)
    PYTHONPATH=src python -m repro.launch.serve --profile mcu --print-config
        # show the resolved config + the layers that produced it

Every pre-profile flag still works and now acts as an override onto the
resolved config (the back-compat contract is golden-snapshot-tested in
``tests/test_serve_backcompat.py``):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduce smoke \
        --requests 8 --new-tokens 16
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduce 100m \
        --budget-mb 64   # weight-swapped prefill via SwapNet
    PYTHONPATH=src python -m repro.launch.serve --multi qwen2.5-3b,gemma2-9b \
        --reduce smoke --budget-mb 48 --rounds 3   # shared-budget multi-tenant
    PYTHONPATH=src python -m repro.launch.serve --multi qwen2.5-3b,gemma2-9b \
        --reduce smoke --budget-mb 48 --executors 2 --priorities 1,8
        # concurrent priority-aware serving: 2 executor threads, urgency
        # classes 1 and 8, preemption at block boundaries
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduce smoke \
        --budget-mb 16 --store quant --precision int4   # packed int4 units
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduce smoke \
        --budget-mb 24 --paged --kv-frac 0.3 --max-batch 8
        # continuous-batching decode: weight blocks and KV pages share ONE budget
"""
from __future__ import annotations

import argparse
import json
import tempfile

import jax
import numpy as np

from repro.config import (ServeConfig, explain_layers, profile_names,
                          resolve_config)
from repro.configs import get_arch
from repro.core.cost_model import DelayModel
from repro.core.multi_model import MultiModelRuntime
from repro.core.runtime import SwappedModel
from repro.core.serving_scheduler import ServingScheduler
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import scale_config
from repro.models.transformer import Model
from repro.serving.batch_engine import BatchDecodeEngine
from repro.serving.control_plane import ControlPlane
from repro.serving.engine import (MultiModelServingEngine, Request,
                                  ServingEngine, pad_prompts)
from repro.serving.metrics import MetricsRegistry
from repro.serving.paged_kv import PagedKVCache


def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q)) if xs else 0.0


# ----------------------------------------------------------------- assembly
def build_runtime(cfg: ServeConfig, workdir: str):
    """Resolved config -> planned MultiModelRuntime + (model, params) refs.
    The ONE construction path every mode shares: the runtime knobs come off
    ``cfg.runtime``, the tenant set off ``cfg.model_names()``."""
    names = cfg.model_names()
    assert names, "config resolved with no arch/models"
    rt = MultiModelRuntime.from_config(cfg)
    refs = {}
    for i, arch in enumerate(names):
        mcfg = scale_config(get_arch(arch), cfg.reduce)
        model = Model(mcfg)
        params = model.init_serving(jax.random.key(i))
        rt.add_model(arch, model, params, workdir)
        refs[arch] = (model, params)
    rt.plan(batch=cfg.workload.requests, seq=cfg.workload.prompt_len)
    return names, rt, refs


def _make_batches(cfg: ServeConfig, refs, seed: int = 0):
    """One padded prefill batch per tenant from the reference workload."""
    rng = np.random.default_rng(seed)
    batches = {}
    for arch, (model, _) in refs.items():
        mcfg = model.cfg
        reqs = [Request(i, list(rng.integers(0, mcfg.vocab_size,
                                             cfg.workload.prompt_len)))
                for i in range(cfg.workload.requests)]
        batches[arch] = pad_prompts(mcfg, reqs)
    return batches


def _build_multi_runtime(cfg: ServeConfig, workdir: str):
    """Legacy --multi setup (>= 2 tenants enforced, as before)."""
    if len(cfg.model_names()) < 2:
        raise SystemExit("--multi wants at least two comma-separated archs")
    return build_runtime(cfg, workdir)


# ------------------------------------------------------------ profile mode
def serve_profile(cfg: ServeConfig) -> None:
    """The unified config-driven path: any number of tenants through the
    priority-aware scheduler, priorities assigned round-robin from the
    profile's workload; with ``runtime.paged`` also drives one generation
    per tenant per round through the continuous-batching engine."""
    classes = [float(p) for p in cfg.workload.priorities]
    budget = int(cfg.runtime.budget_mb * 1e6)
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory() as d:
        names, rt, refs = build_runtime(cfg, d)
        batches = _make_batches(cfg, refs)
        for arch in names:
            rt.forward(arch, batches[arch])     # warm: jit compile per block

        sched = ServingScheduler.from_config(rt, cfg)
        metrics = MetricsRegistry(rt, sched)
        submitted = []
        for round_i in range(cfg.workload.rounds):
            for j, arch in enumerate(names):
                prio = classes[(round_i * len(names) + j) % len(classes)]
                submitted.append(sched.submit(arch, batches[arch],
                                              priority=prio))
                if cfg.runtime.paged:
                    # unique rid per sequence: each model's batch engine
                    # keys admissions by it
                    gen = Request(1000 + round_i * len(names) + j,
                                  list(map(int, rng.integers(
                                      0, refs[arch][0].cfg.vocab_size, 8))),
                                  max_new_tokens=cfg.workload.new_tokens)
                    submitted.append(sched.submit_generate(arch, gen,
                                                           priority=prio))
        for r in submitted:
            r.wait(timeout=600)
        by_class = sched.latency_by_class()
        quantiles = metrics.latency_quantiles()
        sched.shutdown()
        st = rt.stats()
        rt.close()

    print(f"[serve-profile] profile={cfg.profile}: {len(names)} model(s) "
          f"({', '.join(names)}), {cfg.runtime.executors} executor(s), "
          f"store={cfg.runtime.store}"
          f"{'/' + cfg.runtime.precision if cfg.runtime.precision else ''} "
          f"under {cfg.runtime.budget_mb:g} MB: "
          f"{len(submitted)} requests served, "
          f"peak resident {st['peak_resident_mb']:.1f} MB "
          f"({'OK' if st['peak_resident_mb'] * 1e6 <= budget else 'OVER'}), "
          f"preemptions={sched.preemptions}", flush=True)
    print(f"[serve-profile] cache hit rate {st['cache_hit_rate']*100:.1f}% "
          f"({st['cache_hits']} hits / {st['cache_misses']} misses)",
          flush=True)
    for prio in sorted(by_class, reverse=True):
        q = quantiles[prio]
        print(f"[serve-profile]   priority {prio:g}: n={q['n']} "
              f"p50={q['p50_s']*1e3:.1f} ms p99={q['p99_s']*1e3:.1f} ms",
              flush=True)


def serve_http(cfg: ServeConfig) -> None:
    """Profile serving behind the HTTP control plane: build + warm the same
    runtime ``serve_profile`` runs, then serve until ``POST /v1/shutdown``
    (or Ctrl-C). Everything observable in-process is scrapeable at
    ``/metrics``; requests submit/poll/cancel over plain JSON."""
    with tempfile.TemporaryDirectory() as d:
        names, rt, refs = build_runtime(cfg, d)
        batches = _make_batches(cfg, refs)
        for arch in names:
            rt.forward(arch, batches[arch])     # warm: jit compile per block
        sched = ServingScheduler.from_config(rt, cfg)
        metrics = MetricsRegistry(rt, sched)
        cp = ControlPlane(rt, sched, metrics,
                          host=cfg.http.host, port=cfg.http.port,
                          plan_shape=(cfg.workload.requests,
                                      cfg.workload.prompt_len),
                          reduce=cfg.reduce, workdir=d)
        cp.start()
        # the line drivers parse — keep the format stable
        print(f"[serve-http] listening on {cp.url} "
              f"(models: {', '.join(names)}; profile={cfg.profile}; "
              f"POST /v1/shutdown to stop)", flush=True)
        try:
            cp.shutdown_requested.wait()
        except KeyboardInterrupt:
            pass
        cp.stop()
        sched.shutdown()
        st = rt.stats()
        rt.close()
    print(f"[serve-http] shut down cleanly: peak resident "
          f"{st['peak_resident_mb']:.1f} MB, "
          f"cache hit rate {st['cache_hit_rate']*100:.1f}%", flush=True)


# ------------------------------------------------------------- legacy modes
def serve_multi_scheduled(cfg: ServeConfig) -> None:
    """K concurrent executors + priority-aware preemptive scheduling over
    the shared-budget runtime (`core/serving_scheduler.py`): requests carry
    an urgency class (--priorities, assigned round-robin) and are admitted
    by urgency-weighted deadline; low-priority passes yield at block
    boundaries to high-urgency arrivals. Reports per-class p50/p99 latency,
    preemption count, and the lossless check vs each unswapped model."""
    classes = [float(p) for p in cfg.workload.priorities]
    budget = int(cfg.runtime.budget_mb * 1e6)
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory() as d:
        archs, rt, refs = _build_multi_runtime(cfg, d)

        batches, ref_logits = {}, {}
        for arch, (model, params) in refs.items():
            mcfg = model.cfg
            reqs = [Request(i, list(rng.integers(0, mcfg.vocab_size,
                                                 cfg.workload.prompt_len)))
                    for i in range(cfg.workload.requests)]
            batches[arch] = pad_prompts(mcfg, reqs)
            out, _ = jax.jit(model.prefill)(params, batches[arch])
            ref_logits[arch] = np.asarray(out[:, -1:])
            rt.forward(arch, batches[arch])      # warm: jit compile per block

        sched = ServingScheduler.from_config(rt, cfg)
        submitted = []
        for round_i in range(cfg.workload.rounds):
            for j, arch in enumerate(archs):
                prio = classes[(round_i * len(archs) + j) % len(classes)]
                submitted.append(sched.submit(arch, batches[arch],
                                              priority=prio))
        for r in submitted:
            r.wait(timeout=600)
        sched.shutdown()
        st = rt.stats()
        rt.close()

    def _tol(arch):
        # the repo's lossless standard (see serve_multi): residual diffs are
        # XLA fusion order of per-unit vs whole-model jit, not the swap path
        return 1e-4 if refs[arch][0].cfg.dtype == "float32" else 2e-2

    exact = all(
        np.allclose(np.asarray(r.logits), ref_logits[r.model],
                    rtol=_tol(r.model), atol=_tol(r.model))
        for r in submitted
        if rt.models[r.model].store_backend != "quant")
    print(f"[serve-sched] {len(archs)} models, {cfg.runtime.executors} "
          f"executors under {cfg.runtime.budget_mb:.0f} MB: peak resident "
          f"{st['peak_resident_mb']:.1f} MB "
          f"({'OK' if st['peak_resident_mb'] * 1e6 <= budget else 'OVER'}), "
          f"lossless={exact}, preemptions={sched.preemptions}", flush=True)
    by_class = sched.latency_by_class()
    for prio in sorted(by_class, reverse=True):
        lat = [x * 1e3 for x in by_class[prio]]
        print(f"[serve-sched]   priority {prio:g}: n={len(lat)} "
              f"p50={_percentile(lat, 50):.1f} ms "
              f"p99={_percentile(lat, 99):.1f} ms", flush=True)


def _mixed_store_options(cfg: ServeConfig, model, params):
    """With ``--precision mixed`` on the quant store, run the calibration
    pass (repro/calibrate/) and return ``{"plan": PrecisionPlan}`` for the
    SwappedModel's store; None when mixed doesn't apply (other precisions,
    other stores, or a quant-ineligible arch that will fall back to mmap).
    The multi-tenant paths don't need this — MultiModelRuntime.add_model
    calibrates arriving models itself."""
    if (cfg.runtime.precision != "mixed" or cfg.runtime.store != "quant"
            or not model.cfg.quant_eligible):
        return None
    from repro.calibrate import calibrate_model
    _, plan = calibrate_model(model, params, fidelity=cfg.runtime.fidelity,
                              prefetch_depth=cfg.runtime.prefetch_depth)
    hist = plan.histogram()
    print(f"[calibrate] {model.cfg.name}: fidelity {cfg.runtime.fidelity:g} "
          f"-> predicted_err {plan.predicted_err:.2e}, "
          f"stored {plan.stored_bytes/1e6:.2f} MB, units "
          f"fp={hist['fp']} int8={hist['int8']} int4={hist['int4']}",
          flush=True)
    return {"plan": plan}


def serve_paged(cfg: ServeConfig, mcfg, model, params) -> None:
    """Swap-aware continuous-batching decode: weight blocks are planned
    against (1 - kv_frac) of the budget and the KV page pool is sized from
    the rest, BOTH charged to one ledger — growing the decode batch
    genuinely competes with weight-block residency, and page pressure
    preempts the youngest/lowest-priority sequences (recompute on
    re-admission)."""
    budget = int(cfg.runtime.budget_mb * 1e6)
    kv_bytes = int(budget * cfg.runtime.kv_frac)
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d, mode="snet", budget=budget,
                          prefetch_depth=cfg.runtime.prefetch_depth,
                          store_backend=cfg.runtime.store,
                          precision=cfg.runtime.precision,
                          store_options=_mixed_store_options(cfg, model,
                                                             params))
        sm.partition(budget - kv_bytes, DelayModel(), 1,
                     cfg.workload.prompt_len)
        kv = PagedKVCache.for_budget(mcfg, sm.engine.ledger, kv_bytes,
                                     page_tokens=cfg.runtime.page_tokens)
        be = BatchDecodeEngine(sm, kv, max_batch=cfg.runtime.max_batch)
        reqs = [Request(i, list(rng.integers(0, mcfg.vocab_size,
                                             cfg.workload.prompt_len)),
                        max_new_tokens=cfg.workload.new_tokens)
                for i in range(cfg.workload.requests)]
        for r in reqs:
            be.submit(r)
        be.run_all()
        st = be.stats()
        peak = sm.engine.ledger.peak
        sm.close()
    print(f"[serve-paged] {cfg.workload.requests} requests x "
          f"{cfg.workload.new_tokens} new "
          f"tokens under {cfg.runtime.budget_mb:.0f} MB "
          f"(kv_frac={cfg.runtime.kv_frac:g}, {kv.max_pages} pages x "
          f"{kv.page_tokens} tok): {st['tok_per_s']:.2f} tok/s, "
          f"occupancy {st['mean_occupancy']*100:.0f}%, "
          f"preemptions {st['preemptions']:.0f}, "
          f"peak resident {peak/1e6:.1f} MB "
          f"({'OK' if peak <= budget else 'OVER'})", flush=True)
    print(f"[serve-paged] sample output: {reqs[0].output[:12]}", flush=True)


def serve_multi(cfg: ServeConfig) -> None:
    """Two or more models interleaved under ONE weight budget: the paper's
    §6 multi-DNN scenario end-to-end. Verifies the swapped prefill logits
    stay bit-identical to each unswapped model, then reports peak residency
    vs the budget, pipeline overlap efficiency, and cache hit rate."""
    budget = int(cfg.runtime.budget_mb * 1e6)
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory() as d:
        archs, rt, refs = _build_multi_runtime(cfg, d)

        engine = MultiModelServingEngine(rt)
        exact = True
        fidelity = {}
        for round_i in range(cfg.workload.rounds):
            for arch in archs:          # interleave tenants round-robin
                mcfg = refs[arch][0].cfg
                reqs = [Request(i, list(rng.integers(
                            0, mcfg.vocab_size, cfg.workload.prompt_len)))
                        for i in range(cfg.workload.requests)]
                logits = engine.prefill(arch, reqs)
                if round_i == 0:        # lossless vs the unswapped model
                    # (allclose, the repo's standard: swapping itself is
                    # byte-lossless; residual diffs are XLA fusion order of
                    # per-unit vs whole-model jit, not the swap path. The
                    # quant store is NOT lossless — its bounded error is
                    # reported as fidelity instead of asserted exact.)
                    model, params = refs[arch]
                    batch = pad_prompts(model.cfg, reqs)
                    ref, _ = jax.jit(model.prefill)(params, batch)
                    # gate on the model's EFFECTIVE backend: a quant-
                    # ineligible config fell back to the exact mmap store
                    # and must keep its lossless assertion
                    if rt.models[arch].store_backend == "quant":
                        a = np.asarray(logits, np.float64).ravel()
                        b = np.asarray(ref[:, -1:], np.float64).ravel()
                        fidelity[arch] = float(
                            a @ b / max(np.linalg.norm(a)
                                        * np.linalg.norm(b), 1e-30))
                        continue
                    tol = 1e-4 if model.cfg.dtype == "float32" else 2e-2
                    ok = bool(np.allclose(np.asarray(logits),
                                          np.asarray(ref[:, -1:]),
                                          rtol=tol, atol=tol))
                    exact = exact and ok
        st = rt.stats()
        rt.close()

    # mixed backends report BOTH signals: bounded-error fidelity for the
    # quant tenants, the lossless assertion for every exact-store tenant
    parts = []
    if fidelity:
        parts.append(f"fidelity={min(fidelity.values()):.4f}")
    if len(fidelity) < len(archs):
        parts.append(f"lossless={exact}")
    quality = " ".join(parts)
    print(f"[serve-multi] {len(archs)} models under "
          f"{cfg.runtime.budget_mb:.0f} MB "
          f"(store={cfg.runtime.store}): "
          f"peak resident {st['peak_resident_mb']:.1f} MB "
          f"({'OK' if st['peak_resident_mb'] * 1e6 <= budget else 'OVER'}), "
          f"{quality}", flush=True)
    print(f"[serve-multi] cache {st['cache_resident_mb']:.1f}/"
          f"{st['cache_capacity_mb']:.1f} MB, "
          f"hit rate {st['cache_hit_rate']*100:.1f}% "
          f"({st['cache_hits']} hits / {st['cache_misses']} misses)", flush=True)
    for name, ms in st["models"].items():
        print(f"[serve-multi]   {name}: blocks={ms['n_blocks']} m={ms['m']} "
              f"store={ms['store_backend']}/{ms['precision']} "
              f"overlap_eff={ms['overlap_efficiency']*100:.1f}% "
              f"swapped {ms['bytes_swapped_mb']:.1f} MB "
              f"({ms['bytes_logical_mb']:.1f} MB logical)", flush=True)


def serve_single(cfg: ServeConfig) -> None:
    """Single-arch legacy modes: paged decode, swapped prefill, or the
    plain in-memory engine."""
    mcfg = scale_config(get_arch(cfg.arch), cfg.reduce)
    if not mcfg.supports_decode():
        raise SystemExit(f"{mcfg.name} is encoder-only: no decode serving")
    model = Model(mcfg)
    params = model.init_serving(jax.random.key(0))
    rng = np.random.default_rng(0)

    if cfg.runtime.paged:
        serve_paged(cfg, mcfg, model, params)
        return
    if cfg.runtime.budget_mb is not None:
        budget = int(cfg.runtime.budget_mb * 1e6)
        with tempfile.TemporaryDirectory() as d:
            sm = SwappedModel(model, params, d, mode="snet", budget=None,
                              prefetch_depth=cfg.runtime.prefetch_depth,
                              store_backend=cfg.runtime.store,
                              precision=cfg.runtime.precision,
                              store_options=_mixed_store_options(cfg, model,
                                                                 params))
            sm.partition(budget, DelayModel(), cfg.workload.requests,
                         cfg.workload.prompt_len)
            batch = {"tokens": jax.numpy.asarray(
                rng.integers(0, mcfg.vocab_size,
                             (cfg.workload.requests,
                              cfg.workload.prompt_len)),
                jax.numpy.int32)}
            logits, stats = sm.forward(batch)   # warm
            sm.engine.stats.__init__()
            logits, stats = sm.forward(batch)
            sm.close()
        print(f"[serve] swapped prefill: {stats['latency_s']*1e3:.1f} ms, "
              f"peak resident {stats['peak_resident_mb']:.1f} MB "
              f"(budget {cfg.runtime.budget_mb:g} MB), "
              f"blocks={sm.plan.n_blocks}, "
              f"store={stats['store_backend']}"
              f"/{stats['precision']}, "
              f"swapped {stats['bytes_swapped']/1e6:.1f} MB "
              f"({stats['bytes_logical']/1e6:.1f} MB logical, "
              f"{stats['bytes_resident_quantized']/1e6:.1f} MB "
              f"quantized-resident), "
              f"kernel VMEM {stats['vmem_working_set']/1e6:.2f} MB, "
              f"overlap_eff={stats['overlap_efficiency']*100:.1f}%", flush=True)
        return

    engine = ServingEngine(model, params, max_len=cfg.workload.max_len)
    reqs = [Request(i, list(rng.integers(0, mcfg.vocab_size,
                                         cfg.workload.prompt_len)),
                    max_new_tokens=cfg.workload.new_tokens)
            for i in range(cfg.workload.requests)]
    stats = engine.generate(reqs)   # includes compile
    reqs2 = [Request(100 + i, r.prompt, r.max_new_tokens)
             for i, r in enumerate(reqs)]
    stats = engine.generate(reqs2)  # warm numbers
    print(f"[serve] {cfg.workload.requests} requests x "
          f"{cfg.workload.new_tokens} new tokens: "
          f"prefill {stats['prefill_s']*1e3:.1f} ms, "
          f"{stats['tok_per_s']:.1f} tok/s decode", flush=True)
    print(f"[serve] sample output: {reqs2[0].output[:12]}", flush=True)


# ------------------------------------------------------------- entry point
def build_parser() -> argparse.ArgumentParser:
    """Every value-bearing flag defaults to None: only EXPLICITLY passed
    flags enter the CLI layer, everything else resolves through
    defaults -> profile -> env (see ``repro.config.layering``)."""
    ap = argparse.ArgumentParser(
        description="SwapNet serving driver (layered config: defaults -> "
                    "profile -> SWAPNET_* env -> CLI)")
    ap.add_argument("--profile", default=None,
                    help=f"device-class deployment profile "
                         f"({', '.join(profile_names())}); every other flag "
                         f"overrides on top")
    ap.add_argument("--print-config", action="store_true",
                    help="print the resolved config (and the layers that "
                         "produced it) as JSON, then exit")
    ap.add_argument("--http", action="store_true", default=None,
                    help="serve behind the HTTP control plane "
                         "(submit/poll/cancel, /healthz, /metrics) until "
                         "POST /v1/shutdown")
    ap.add_argument("--http-host", default=None,
                    help="control-plane bind host (default 127.0.0.1)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="control-plane port (0 = ephemeral; the bound "
                         "port is printed on startup)")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--multi", default=None,
                    help="comma-separated archs served interleaved under one "
                         "shared weight budget (requires --budget-mb)")
    ap.add_argument("--reduce", default=None, choices=["smoke", "100m", "full"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--new-tokens", type=int, default=None)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None,
                    help="multi-tenant round-robin passes (repeat requests "
                         "exercise the shared block cache)")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="pipeline residency m (1=serial, 2=double buffer)")
    ap.add_argument("--executors", type=int, default=None,
                    help="concurrent executor threads for --multi serving "
                         "(>1 enables the priority-aware preemptive "
                         "scheduler; each model's blocks are planned "
                         "against a 1/K budget slice so K pipelines co-fit)")
    ap.add_argument("--priorities", default=None,
                    help="comma-separated urgency classes assigned "
                         "round-robin to --multi requests (e.g. '1,8'; "
                         "higher = more urgent — admitted earlier and "
                         "preempts lower classes at block boundaries)")
    ap.add_argument("--rebalance", action="store_true", default=None,
                    help="re-split the block budget (MultiDNNScheduler "
                         "Eq. 1) whenever the queued urgency mix changes")
    ap.add_argument("--cache-frac", type=float, default=None,
                    help="fraction of the budget reserved for the shared "
                         "hot-block cache (multi-tenant mode)")
    ap.add_argument("--budget-mb", type=float, default=None,
                    help="SwapNet weight budget: stream blocks during prefill")
    ap.add_argument("--paged", action="store_true", default=None,
                    help="continuous-batching decode through the paged KV "
                         "cache (requires --budget-mb): weight blocks and "
                         "KV pages share one ledger, sequences admit/retire "
                         "at every decode step")
    ap.add_argument("--kv-frac", type=float, default=None,
                    help="fraction of --budget-mb reserved for KV pages in "
                         "--paged mode (the rest plans weight blocks)")
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="tokens per KV page (one page spans all layers)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="decode batch slots for --paged continuous batching")
    ap.add_argument("--store", default=None,
                    choices=["mmap", "rawio", "quant", "directio"],
                    help="block-store backend: mmap (zero-copy, lossless), "
                         "rawio (read()-based ablation arm), quant (per-"
                         "channel quantized swap units kept quantized-"
                         "resident: 2-D matmul weights stream through the "
                         "fused dequant-matmul kernel, 4-8x less swap-in "
                         "I/O, bounded error), directio (O_DIRECT lossless "
                         "reads that bypass the page cache — no hidden "
                         "double-caching of swapped bytes under a tight "
                         "budget; falls back to buffered reads on "
                         "filesystems without O_DIRECT)")
    ap.add_argument("--precision", default=None,
                    choices=["int8", "int4", "mixed"],
                    help="quant-store unit precision override (default: the "
                         "arch config's swap_precision; int4 packs two "
                         "weights per byte — half the swap bytes of int8 "
                         "at a max|w[:,c]|/14 per-channel error bound; "
                         "mixed runs the sensitivity calibration pass "
                         "(repro/calibrate/) and assigns int4/int8/fp PER "
                         "UNIT against the --fidelity target)")
    ap.add_argument("--fidelity", type=float, default=None,
                    help="max rel-L2 model-output error the mixed-precision "
                         "plan may spend (e.g. 1e-2); required with "
                         "--precision mixed")
    return ap


def cli_overrides(args: argparse.Namespace) -> dict:
    """The CLI layer: only flags the user actually passed, mapped onto the
    nested config schema. ``--arch`` and ``--multi`` clear each other so a
    CLI choice cleanly overrides a profile's tenant set."""
    ov: dict = {}

    def put(section, key, value):
        if value is not None:
            ov.setdefault(section, {})[key] = value

    if args.arch is not None:
        ov["arch"] = args.arch
        ov["models"] = []
    if args.multi is not None:
        ov["models"] = [a.strip() for a in args.multi.split(",") if a.strip()]
        ov["arch"] = None
    if args.reduce is not None:
        ov["reduce"] = args.reduce
    put("workload", "requests", args.requests)
    put("workload", "prompt_len", args.prompt_len)
    put("workload", "new_tokens", args.new_tokens)
    put("workload", "max_len", args.max_len)
    put("workload", "rounds", args.rounds)
    if args.priorities is not None:
        ov.setdefault("workload", {})["priorities"] = [
            float(p) for p in args.priorities.split(",")]
    put("runtime", "budget_mb", args.budget_mb)
    put("runtime", "prefetch_depth", args.prefetch_depth)
    put("runtime", "cache_frac", args.cache_frac)
    put("runtime", "executors", args.executors)
    put("runtime", "store", args.store)
    put("runtime", "precision", args.precision)
    put("runtime", "fidelity", args.fidelity)
    put("runtime", "paged", args.paged)
    put("runtime", "kv_frac", args.kv_frac)
    put("runtime", "page_tokens", args.page_tokens)
    put("runtime", "max_batch", args.max_batch)
    put("scheduler", "rebalance", args.rebalance)
    put("http", "enabled", args.http)
    put("http", "host", args.http_host)
    put("http", "port", args.http_port)
    return ov


def dispatch_mode(cfg: ServeConfig) -> str:
    """Which serving path a resolved config takes — pure routing, snapshot-
    tested for back-compat (tests/test_serve_backcompat.py)."""
    if cfg.http.enabled:
        return "http"
    if cfg.profile:
        return "profile"
    if cfg.models:
        if cfg.runtime.budget_mb is None:
            raise SystemExit("--multi requires --budget-mb")
        return "multi-scheduled" if cfg.runtime.executors > 1 else "multi"
    if not cfg.arch:
        raise SystemExit("need --arch (single model), --multi a,b, or "
                         "--profile <name>")
    if cfg.runtime.paged:
        if cfg.runtime.budget_mb is None:
            raise SystemExit("--paged requires --budget-mb")
        return "paged"
    return "swapped-prefill" if cfg.runtime.budget_mb is not None else "plain"


def run_config(cfg: ServeConfig) -> None:
    mode = dispatch_mode(cfg)
    if mode == "http":
        serve_http(cfg)
    elif mode == "profile":
        serve_profile(cfg)
    elif mode == "multi-scheduled":
        serve_multi_scheduled(cfg)
    elif mode == "multi":
        serve_multi(cfg)
    else:                       # paged / swapped-prefill / plain
        serve_single(cfg)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    overlay = cli_overrides(args)
    cfg = resolve_config(profile=args.profile, cli=overlay)
    if args.print_config:
        layers = [(name, ov) for name, ov in
                  explain_layers(profile=args.profile, cli=overlay)
                  if name != "defaults"]
        print(json.dumps({"resolved": cfg.to_dict(),
                          "mode": dispatch_mode(cfg),
                          "layers": dict(layers)}, indent=2, sort_keys=True))
        return
    enable_compile_cache()
    run_config(cfg)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Chip smoke test: qwen2.5-3b swapped serving at published width on one TPU.

    python3 chip_smoke.py          # from the repository root, on a TPU host

One process runs every phase at qwen2.5-3b's published width (36 layers,
d_model 2048, vocab 151936, bf16, weights from ``jax.random.key(0)``):

  a  kernels: ``ops.paged_attention`` and ``ops.swap_linear_q`` at the
     model's shapes against their ``kernels/ref.py`` oracles; the lowered
     HLO of each must hold a ``tpu_custom_call``, so no reference stood in;
  b  swapped prefill on the mmap store under a 2000 MB budget, which the
     stored units exceed ~3.4x, against ``jax.jit(Model.prefill)`` (see
     ``agree`` for the check);
  c  the same prefill on the int8 quant store, fused (``swap_linear_q``
     inside the model): cosine fidelity above 0.98;
  d  serving: MultiModelRuntime -> ServingScheduler -> BatchDecodeEngine as
     ``serve --profile workstation --arch qwen2.5-3b --reduce full
     --budget-mb 2000 --store mmap`` builds it. Prefill and generate
     requests must all finish, the scheduled prefills must equal phase b's
     pass bit for bit, and the first paged decode step must agree with
     ``Model.decode_step`` on a contiguous cache.

Each phase prints its checks, its compile and run seconds, and the device's
``peak_bytes_in_use`` beside the ledger peak. The last line is
``{"ok": true, "device": {...}}``; a failed phase exits 1 without it, and a
host without a TPU exits 1 before any phase runs. The budget in (b)-(d) is
a ledger budget: the device also holds the initialised parameters the
references need, so the device peak is recorded, not checked.
"""
from __future__ import annotations

import functools
import json
import sys
import tempfile
import time
import traceback
from dataclasses import replace
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "qwen2.5-3b"
TOL = 2e-2            # the repo's bf16 tolerance (launch/serve.py)
NOISE_RATIO = 1.5     # swapped RMS error vs fp32 over the reference's own
MIN_COSINE = 0.98     # the int8 store's fidelity bound (README)
RATIO = (2.3, 5.8)    # the paper's range of weights over budget
BUDGET_MB = 2000      # ledger budget of phases b-d
REQUESTS = 2          # prefill requests, and as many generate requests
PROMPT_LEN = 64
NEW_TOKENS = 6
PAGE_TOKENS = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))


def require_mosaic(name: str, hlo: str) -> None:
    check("tpu_custom_call" in hlo,
          f"{name}: lowered HLO holds no tpu_custom_call (a reference ran)")


def device_peak_mb(jax) -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 1e6


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    import jax
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def fp32_logits(model, params, tokens, step=None):
    """The exact result every bf16 path approximates: the last-position
    logits of ``tokens`` [B, S] and, given ``step = (token [B, 1], pos [B])``
    with pos == S, those of one decode step after them. Computed layer by
    layer in fp32 at the highest matmul precision from the same bf16
    weights (qwen2.5-3b is one scanned stack of dense layers)."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import rms_norm
    from repro.models.transformer import Model, apply_layer
    cfg = replace(model.cfg, dtype="float32")
    m32 = Model(cfg)
    (seg,) = model.plan
    check(seg.scanned and seg.kind == "dense", f"unexpected layer plan {seg}")

    def up(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    @functools.partial(jax.jit, static_argnames="mode")
    def layer(lp, x, pos, cache, dpos, mode):
        with jax.default_matmul_precision("highest"):
            return apply_layer(cfg, "dense", up(lp), x, pos, False, cache,
                               dpos, mode)[:2]

    @jax.jit
    def head(p, x):
        with jax.default_matmul_precision("highest"):
            h = rms_norm(x, p["final_norm"].astype(jnp.float32), cfg.norm_eps,
                         plus_one=cfg.post_norms)
            return m32._head(p, h)

    embed = jax.jit(m32._embed, static_argnums=2)
    stack = params["segments"][0]
    x, pos = embed(params, {"tokens": tokens}, "prefill")
    caches = []
    for i in range(seg.n):
        lp = jax.tree.map(lambda a: a[i], stack)
        x, c = layer(lp, x, pos, None, None, mode="prefill")
        caches.append(jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, 1), (0, 0), (0, 0))), c))
    out = [head(params, x[:, -1:])]
    if step is not None:
        token, dpos = step
        x, pos = embed(params, {"token": token, "pos": dpos}, "decode")
        for i in range(seg.n):
            lp = jax.tree.map(lambda a: a[i], stack)
            x, _ = layer(lp, x, pos, caches[i], dpos, mode="decode")
        out.append(head(params, x))
    return [np.asarray(o, np.float32) for o in out]


def agree(name: str, got, ref, truth) -> str:
    """Check a swapped result against the jitted whole-model reference.

    Two bf16 programs that round in different places drift apart over 36
    layers, so the reference is itself off the exact fp32 result. The
    swapped result must stay within ``TOL`` of the reference relative to
    the logits' scale, and its RMS error against the fp32 result may be at
    most ``NOISE_RATIO`` times the reference's own."""
    got, ref, truth = (np.asarray(a, np.float64) for a in (got, ref, truth))
    check(got.shape == ref.shape == truth.shape,
          f"{name}: shapes {got.shape} / {ref.shape} / {truth.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite logits")
    diff = float(np.abs(got - ref).max())
    rel = diff / max(float(np.abs(ref).max()), 1e-30)
    e_got = float(np.sqrt(np.mean(np.square(got - truth))))
    e_ref = float(np.sqrt(np.mean(np.square(ref - truth))))
    close = bool(np.allclose(got, ref, rtol=TOL, atol=TOL))
    msg = (f"max|diff| vs jit {diff:.3e} = {rel:.3e} of max|logit| "
           f"(<= {TOL}); allclose({TOL}) {close}; RMS error vs fp32 "
           f"{e_got:.3e}, jit's own {e_ref:.3e} (ratio "
           f"{e_got / max(e_ref, 1e-30):.3f} <= {NOISE_RATIO})")
    check(rel <= TOL and e_got <= NOISE_RATIO * e_ref, f"{name}: {msg}")
    return msg


# ------------------------------------------------------------------ (a)
def run_kernel(name, wrapper, args, kwargs, oracle):
    """AOT-compile the ops wrapper, require the Mosaic kernel in its HLO,
    run it once and compare with the oracle (one jitted program) at full
    matmul precision."""
    import jax
    t0 = time.perf_counter()
    lowered = wrapper.lower(*args, **kwargs)
    require_mosaic(name, lowered.as_text())
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    got, t_run = timed(compiled, *args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(oracle, **kwargs))(*args)
    err = rel_err(got, want)
    check(got.shape == want.shape and np.isfinite(np.asarray(got, np.float32)).all(),
          f"{name}: shape {got.shape} vs {want.shape} or non-finite output")
    check(err <= TOL, f"{name}: max|diff|/max|ref| = {err:.3e} > {TOL}")
    log(f"[a] {name}: tpu_custom_call=yes rel_err={err:.3e} "
        f"compile={t_compile:.2f}s run={t_run * 1e3:.2f}ms "
        f"(case {time.perf_counter() - t0:.1f}s)")


def phase_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    T, pages = 16, 64
    rng = np.random.default_rng(0)
    for B in (1, 8):
        kq, kk, kv_ = jax.random.split(jax.random.key(B), 3)
        q = jax.random.normal(kq, (B, H, hd), jnp.bfloat16)
        kp = jax.random.normal(kk, (KV, pages + 1, T, hd),
                               jnp.bfloat16).at[:, 0].set(0)
        vp = jax.random.normal(kv_, (KV, pages + 1, T, hd),
                               jnp.bfloat16).at[:, 0].set(0)
        lens = rng.integers(1, 6 * T, B)
        NP = int(-(-lens.max() // T))
        ids = rng.permutation(np.arange(1, pages + 1))
        table = np.zeros((B, NP), np.int32)
        for b, n in enumerate(-(-lens // T)):
            table[b, :n] = ids[b * NP:b * NP + n]
        run_kernel(f"paged_attention B={B} KV={KV} G={H // KV} hd={hd} T={T}",
                   ops.paged_attention,
                   (q, kp, vp, jnp.asarray(table),
                    jnp.asarray(lens, jnp.int32)), {},
                   ref.paged_attention_ref)
    for bits, N in ((8, F), (4, F), (8, V)):
        kx, kw, ks = jax.random.split(jax.random.key(bits + N), 3)
        qw = jax.random.randint(kw, (D // (8 // bits), N), -128, 128,
                                jnp.int8)
        scales = jax.random.uniform(ks, (N,), jnp.float32, 0.5, 1.5) / (
            127.0 * D ** 0.5)
        for M in (1, 128):
            x = jax.random.normal(kx, (M, D), jnp.bfloat16)
            run_kernel(f"swap_linear_q int{bits} {M}x{D}x{N}",
                       ops.swap_linear_q, (x, qw, scales), {"bits": bits},
                       ref.swap_linear_q_ref)


# ------------------------------------------------------------------ (b)
def serve_config():
    """The resolved config of ``serve --profile workstation --arch ...``."""
    from repro.config import resolve_config
    from repro.launch.serve import build_parser, cli_overrides
    args = build_parser().parse_args([
        "--profile", "workstation", "--arch", ARCH,
        "--reduce", "full", "--budget-mb", str(BUDGET_MB),
        "--store", "mmap", "--requests", str(REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--new-tokens", str(NEW_TOKENS),
        "--page-tokens", str(PAGE_TOKENS), "--rounds", "1"])
    return resolve_config(profile=args.profile, env={},
                          cli=cli_overrides(args))


def phase_prefill(ctx: dict) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import build_runtime
    cfg = serve_config()
    budget = BUDGET_MB * 10**6
    ctx["workdir"] = tempfile.TemporaryDirectory()
    t0 = time.perf_counter()
    _, rt, refs = build_runtime(cfg, ctx["workdir"].name)
    t_build = time.perf_counter() - t0
    model, params = refs[ARCH]
    ctx.update(cfg=cfg, rt=rt, model=model, params=params, budget=budget)
    mc, sm = model.cfg, rt.models[ARCH]
    stored = sum(sm.store.stored_nbytes(n) for n in sm.store.order)
    ratio = stored / budget
    log(f"[b] {ARCH}: {mc.n_layers} layers, d_model {mc.d_model}, vocab "
        f"{mc.vocab_size}, {mc.dtype}; stored units {stored / 1e6:.1f} MB = "
        f"{ratio:.2f}x the {BUDGET_MB} MB budget; runtime built in "
        f"{t_build:.1f}s, {sm.plan.n_blocks} blocks, m={sm.plan.m}")
    check(RATIO[0] <= ratio <= RATIO[1],
          f"weights/budget {ratio:.2f}x outside {RATIO}")

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, mc.vocab_size, (REQUESTS, PROMPT_LEN)),
        jnp.int32)}
    t0 = time.perf_counter()
    ref_fn = jax.jit(model.prefill).lower(params, batch).compile()
    t_ref_compile = time.perf_counter() - t0
    (want, _), t_ref = timed(ref_fn, params, batch)
    want = np.asarray(want, np.float32)
    t0 = time.perf_counter()
    (truth,) = fp32_logits(model, params, batch["tokens"])
    t_truth = time.perf_counter() - t0
    (got, _), t_first = timed(rt.forward, ARCH, batch)
    (got2, stats), t_warm = timed(rt.forward, ARCH, batch)
    got = np.asarray(got, np.float32)
    ctx.update(batch=batch, ref_logits=want, swapped_logits=got)
    check(got.shape == (REQUESTS, 1, mc.vocab_size),
          f"logits shape {got.shape}")
    check(np.array_equal(got, np.asarray(got2, np.float32)),
          "two swapped passes disagree")
    fidelity = agree("swapped prefill", got, want, truth)
    peak = rt.ledger.peak
    check(peak <= budget, f"ledger peak {peak / 1e6:.1f} MB over budget")
    log(f"[b] swapped prefill (mmap) B={REQUESTS} S={PROMPT_LEN}:"
        f" {fidelity}; fp32 reference {t_truth:.1f}s; first pass "
        f"{t_first:.2f}s (compile included), warm pass {t_warm:.2f}s; "
        f"reference compile "
        f"{t_ref_compile:.1f}s run {t_ref * 1e3:.1f}ms; swapped "
        f"{stats['bytes_swapped'] / 1e6:.1f} MB; ledger peak "
        f"{peak / 1e6:.1f} MB <= {BUDGET_MB} MB; device peak "
        f"{device_peak_mb(jax):.1f} MB")


# ------------------------------------------------------------------ (c)
def phase_quant(ctx: dict) -> None:
    import jax
    from repro.core.cost_model import DelayModel
    from repro.core.runtime import SwappedModel
    budget, batch, want = ctx["budget"], ctx["batch"], ctx["ref_logits"]
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        sm = SwappedModel(ctx["model"], ctx["params"], d, budget=budget,
                          prefetch_depth=ctx["cfg"].runtime.prefetch_depth,
                          store_backend="quant", precision="int8")
        sm.partition(budget, DelayModel(), REQUESTS, PROMPT_LEN)
        t_build = time.perf_counter() - t0
        try:
            (got, _), t_first = timed(sm.forward, batch)
            (_, stats), t_warm = timed(sm.forward, batch)
            peak = sm.engine.ledger.peak
        finally:
            sm.close()
    a = np.asarray(got, np.float64).ravel()
    b = np.asarray(want, np.float64).ravel()
    cos = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
    check(np.isfinite(a).all(), "non-finite quantized logits")
    check(stats["bytes_resident_quantized"] > 0,
          "no unit stayed quantized: the fused kernel never ran")
    check(cos > MIN_COSINE, f"int8 cosine fidelity {cos:.4f} <= {MIN_COSINE}")
    check(peak <= budget, f"ledger peak {peak / 1e6:.1f} MB over budget")
    log(f"[c] swapped prefill (quant int8, fused): cosine vs jit {cos:.5f} "
        f"(> {MIN_COSINE}); store built in {t_build:.1f}s, "
        f"{sm.plan.n_blocks} blocks; first pass {t_first:.2f}s, warm pass "
        f"{t_warm:.2f}s; swapped {stats['bytes_swapped'] / 1e6:.1f} MB "
        f"({stats['bytes_resident_quantized'] / 1e6:.1f} MB stayed "
        f"quantized); ledger peak {peak / 1e6:.1f} MB; device peak "
        f"{device_peak_mb(jax):.1f} MB")


# ------------------------------------------------------------------ (d)
def phase_serving(ctx: dict) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.serving_scheduler import ServingScheduler
    from repro.serving.engine import Request
    from repro.serving.kv_cache import pad_prefill_cache
    cfg, rt, model, params = ctx["cfg"], ctx["rt"], ctx["model"], ctx["params"]
    budget, vocab = ctx["budget"], model.cfg.vocab_size
    sm = rt.models[ARCH]
    first: dict = {}
    step = sm.decode_step_paged

    def capture(batch, view):           # the first paged step, as it ran
        logits = step(batch, view)
        if not first:
            first.update(logits=np.asarray(logits, np.float32),
                         token=np.asarray(batch["token"]),
                         pos=np.asarray(batch["pos"]),
                         rids=list(view.seq_ids))
        return logits

    sm.decode_step_paged = capture
    rng = np.random.default_rng(1)
    gens, submitted = {}, []
    prios = cfg.workload.priorities
    t0 = time.perf_counter()
    sched = ServingScheduler.from_config(rt, cfg)
    try:
        for i in range(REQUESTS):
            prio = prios[i % len(prios)]
            submitted.append(sched.submit(ARCH, ctx["batch"], priority=prio))
            g = Request(1000 + i, [int(t) for t in rng.integers(
                0, vocab, PROMPT_LEN)], max_new_tokens=NEW_TOKENS)
            gens[g.rid] = g
            submitted.append(sched.submit_generate(ARCH, g, priority=prio))
        for r in submitted:
            r.wait(timeout=900)
    finally:
        sched.shutdown()
        del sm.decode_step_paged
    t_serve = time.perf_counter() - t0
    for r in submitted:     # same layer programs, same inputs: same bits
        if r.kind == "prefill":
            check(np.array_equal(np.asarray(r.logits, np.float32),
                                 ctx["swapped_logits"]),
                  f"scheduled prefill {r.rid} differs from phase b's pass")
    for g in gens.values():
        check(len(g.output) == NEW_TOKENS,
              f"request {g.rid} emitted {len(g.output)} of "
              f"{NEW_TOKENS} tokens")
    peak = rt.ledger.peak
    check(peak <= budget, f"ledger peak {peak / 1e6:.1f} MB over budget")
    be = rt.batch_engine(ARCH)

    check(first, "no paged decode step ran")
    prompts = [gens[r].prompt for r in first["rids"]]
    check(all(p == len(q) for p, q in zip(first["pos"], prompts)),
          "first paged step is not the first decode after prefill")
    n = len(prompts)
    t0 = time.perf_counter()
    tokens = jnp.asarray(prompts, jnp.int32)
    dstep = {"token": jnp.asarray(first["token"]),
             "pos": jnp.asarray(first["pos"])}
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens})
    cache = pad_prefill_cache(model, cache, PROMPT_LEN + 8, n)
    want, _ = jax.jit(model.decode_step)(params, cache, dstep)
    _, truth = fp32_logits(model, params, tokens,
                           (dstep["token"], dstep["pos"]))
    t_ref = time.perf_counter() - t0
    fidelity = agree("first paged decode step", first["logits"], want, truth)
    st = be.stats()
    log(f"[d] serving (workstation profile, {cfg.runtime.executors} "
        f"executors, paged, {be.kv.max_pages} pages x {be.kv.page_tokens} "
        f"tok): {REQUESTS} prefill + {len(gens)} generate requests "
        f"served in {t_serve:.1f}s; tokens {[len(g.output) for g in gens.values()]}"
        f"; prefill requests bit-identical to phase b's pass; first paged "
        f"step (batch {n}) vs Model.decode_step on a contiguous cache: "
        f"{fidelity} (references {t_ref:.1f}s); decode steps "
        f"{st['decode_steps']:.0f}, preemptions {sched.preemptions}; ledger "
        f"peak {peak / 1e6:.1f} MB <= {BUDGET_MB} MB; device peak "
        f"{device_peak_mb(jax):.1f} MB")


# ------------------------------------------------------------------ main
PHASES = (("a", "kernels", lambda ctx: phase_kernels(ctx["arch"])),
          ("b", "swapped prefill (mmap)", phase_prefill),
          ("c", "swapped prefill (quant int8)", phase_quant),
          ("d", "serving", phase_serving))


def run() -> bool:
    """Every phase in order; c and d build on what b leaves in ``ctx``."""
    from repro.configs import get_arch
    ctx: dict = {"arch": get_arch(ARCH)}
    failed = []
    for p, name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            fn(ctx)
        except Exception:       # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            failed.append(p)
            log(f"[{p}] {name}: FAILED after {time.perf_counter() - t0:.1f}s")
            continue
        log(f"[{p}] {name}: passed in {time.perf_counter() - t0:.1f}s")
    if "rt" in ctx:
        ctx["rt"].close()
        ctx["workdir"].cleanup()
    return not failed


def main() -> int:
    log(f"[smoke] compile cache: {enable_compile_cache()}")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    log(f"[smoke] device {dev.device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    ok = run()
    log(f"[smoke] total {time.perf_counter() - t0:.1f}s")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

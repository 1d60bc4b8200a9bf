"""SwappedModel: end-to-end swapped inference of any repro model (paper §3).

Splits a model into swappable units (embedding, each layer, head), stores
them via a pluggable block store (``store_backend``: mmap | rawio | quant,
see repro/store/), and executes a forward pass block-by-block under a
memory budget with a depth-m prefetch pipeline (m=2 is the paper's double
buffer; deeper pipelines absorb swap-in jitter). With the default (mmap)
backend the output is bit-identical to the in-memory model (lossless — the
paper's headline property); the quant backend trades a documented bounded
quantization error for 4x (int8) to 8x (int4) less swap-in I/O, keeps
units quantized-RESIDENT (fp is never materialized for MLP/head weights —
they stream through the fused dequant-matmul kernel; other consumers
dequantize at use), and lets the block planner pack more layers per block
since the ledger is charged payload bytes.

Engines may share a MemoryLedger and BlockCache with other models — the
multi-DNN serving path (core/multi_model.py) relies on this to keep several
co-resident models under ONE budget while hot units stay cached.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import (DelayModel, LayerInfo, layer_flops,
                                   resident_infos)
from repro.core.partition import BlockPlan, PartitionPlanner
from repro.core.swap_engine import BlockCache, MemoryLedger, SwapEngine
from repro.kernels.qtensor import (QuantizedTensor, cast_unit_params,
                                   materialize_tree)
from repro.kernels.swap_linear import vmem_bytes
from repro.models.layers import linear, rms_norm, softcap
from repro.store import build_store
from repro.tracing import span
from repro.models.transformer import (Model, apply_layer_jit,
                                     apply_layer_paged)


@functools.partial(jax.jit, static_argnums=(0,))
def head_logits(cfg, final_norm, w, h):
    """Final norm + lm_head projection to fp32 logits, one compiled program
    (as ``apply_layer_jit`` is for a layer). A quantized head streams
    through the fused kernel (vocab projections are the odd-shaped case
    the padded swap_linear grid covers)."""
    h = rms_norm(h, final_norm.astype(h.dtype), cfg.norm_eps,
                 plus_one=cfg.post_norms)
    if isinstance(w, QuantizedTensor):
        logits = linear(h.astype(jnp.float32), w)
    else:
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    return softcap(logits, cfg.final_logit_softcap)


def swap_schedule(eng: SwapEngine, blocks, unit_names: Sequence[str], m: int):
    """Drive the depth-m prefetch pipeline over ``blocks``.

    Yields (block_index, lo, hi, handle) with the handle's block resident;
    swap-out happens after the caller's body returns control. Issues the load
    of block i only once block i-m has been freed, so at most m blocks are
    ever resident — the executor-side mirror of partition.simulate_pipeline.
    """
    m = max(m, 1)
    futs: deque = deque()
    issued = 0

    def pump(limit: int) -> None:
        nonlocal issued
        while issued < min(limit, len(blocks)):
            lo, hi = blocks[issued]
            futs.append(eng.prefetch(list(unit_names[lo:hi])))
            issued += 1

    pump(m)
    try:
        for bi, (lo, hi) in enumerate(blocks):
            handle = eng.wait(futs.popleft())
            try:
                yield bi, lo, hi, handle
            finally:
                eng.swap_out(handle)
            pump(bi + 1 + m)
    finally:
        # Abandoned mid-run (body raised, wait raised, or caller closed the
        # generator): drain in-flight prefetches so their ledger bytes and
        # cache leases are released — a shared ledger must not keep charging
        # a failed request's blocks against every other tenant's budget.
        while futs:
            try:
                eng.swap_out(futs.popleft().result())
            except Exception:
                continue


@dataclass
class PassState:
    """A swapped forward pass, resumable at block boundaries.

    The serving scheduler's preemption unit: a pass that yields between
    blocks carries everything needed to continue later — the activation,
    the position carrier, and the index of the next block — so a preempted
    request re-executes NOTHING on resume (bit-identical to an
    uninterrupted pass). ``blocks`` AND the pipeline depth ``m`` are
    snapshotted at pass start: a live budget re-plan
    (``MultiModelRuntime.replan_budgets``) only affects passes that start
    after it, never one already in flight — resuming old blocks at a new
    plan's (possibly deeper) m could hold more bytes than the old plan's
    budget slice promised."""
    blocks: List[Tuple[int, int]]
    m: int = 2
    x: Any = None
    positions: Any = None
    next_block: int = 0
    t_active: float = 0.0     # wall clock while actually executing (not paused)
    preemptions: int = 0
    logits: Any = None
    caches: Any = None        # layer_id -> prefill cache (collect_cache=True)

    @property
    def done(self) -> bool:
        return self.next_block >= len(self.blocks)


@dataclass
class Unit:
    name: str
    kind: str                 # embed | head | dense | moe | mamba2 | rwkv6 | shared_attn
    layer_id: Optional[int]
    params: dict


def split_units(model: Model, params: dict) -> List[Unit]:
    """The paper's get_layers(Net): one-time layer-wise division."""
    cfg = model.cfg
    units: List[Unit] = []
    head_p = {k: params[k] for k in ("embed", "frontend", "mask_emb")
              if k in params}
    if head_p:
        units.append(Unit("embed", "embed", None, head_p))
    for si, seg in enumerate(model.plan):
        if not seg.scanned:
            units.append(Unit("shared_attn", "shared_attn",
                              seg.layer_ids[0], params["shared_attn"]))
            continue
        stacked = params["segments"][si]
        for j, lid in enumerate(seg.layer_ids):
            p = jax.tree.map(lambda a, _j=j: np.asarray(a[_j]), stacked)
            units.append(Unit(f"layer{lid:03d}_{seg.kind}", seg.kind, lid, p))
    tail = {"final_norm": params["final_norm"]}
    if "lm_head" in params:
        tail["lm_head"] = params["lm_head"]
    elif cfg.tie_embeddings and cfg.embed_inputs:
        # tied head: materialize the transposed table in the head unit so the
        # embed block need not stay resident (storage, not memory, pays)
        tail["lm_head"] = np.asarray(params["embed"]).T.copy()
    units.append(Unit("head", "head", None, tail))
    return units


def unit_infos(model: Model, units: Sequence[Unit], batch: int,
               seq: int) -> List[LayerInfo]:
    """Model info table rows (paper Table 2) aligned 1:1 with units."""
    cfg = model.cfg
    rows = []
    for u in units:
        size = sum(np.asarray(l).nbytes for l in jax.tree.leaves(u.params))
        depth = len(jax.tree.leaves(u.params))
        if u.kind == "embed":
            f = 2.0 * batch * seq * cfg.d_model
        elif u.kind == "head":
            has_head = "lm_head" in u.params
            f = 2.0 * batch * cfg.d_model * cfg.vocab_size * (1 if has_head else 1)
        else:
            kind = "dense" if u.kind == "shared_attn" else u.kind
            f = layer_flops(cfg, kind, u.params, batch, seq)
        rows.append(LayerInfo(u.name, int(size), depth, float(f)))
    return rows


def resolve_backend(store_backend: Optional[str], mode: str) -> str:
    """Default the store backend and reject nonsensical combinations: the
    engine's ablation ``mode`` flags reinterpret the RAW file format, so
    they compose only with the mmap backend (rawio IS the copy_in arm;
    quant files cannot be read through the raw paths)."""
    backend = store_backend or "mmap"
    if backend != "mmap" and mode != "snet":
        raise ValueError(f"store backend {backend!r} requires mode='snet' "
                         f"(got mode={mode!r})")
    return backend


def store_opts(backend: str, gpu_dispatch: bool, precision: str = "int8",
               fused: bool = False) -> dict:
    """Per-backend build options derived from the executor flags.

    For the quant backend, ``precision`` picks the swap-unit bit-width
    (int8 | int4, or ``mixed`` for a per-unit calibration plan — the plan
    itself arrives via the ``store_options`` overlay as ``plan=...``, and
    the store keeps any unit the plan omits raw) and ``fused`` turns
    eager dequant OFF: units come back as QuantizedTensor leaves that
    linear layers stream through the fused dequant-matmul kernel
    (non-matmul consumers dequantize at use)."""
    if backend == "rawio":
        return {"gpu_dispatch": gpu_dispatch}
    if backend == "quant":
        assert precision in ("int8", "int4", "mixed"), precision
        return {"bits": 4 if precision == "int4" else 8, "eager": not fused}
    if backend == "faulty":
        # chaos arm: fault injection over the zero-copy path by default;
        # callers tune inner/p/seed via the ``store_options`` pass-through
        return {"inner": "mmap"}
    return {}


def kernel_vmem_working_set(precision: str, dtype: str = "bfloat16",
                            block_m: int = 256, block_n: int = 256,
                            block_k: int = 512) -> int:
    """Per-kernel VMEM working set of the weight-stream matmul at the
    default tiling for a store precision (the figure SwapStats reports:
    the fused path shrinks the weight window 2x int8 / 4x int4)."""
    item = jnp.dtype(dtype).itemsize
    # "mixed" reports the int8 window: the CONSERVATIVE per-kernel figure
    # (any int4-assigned unit streams a strictly smaller one)
    w_bits = {"fp": None, "int8": 8, "int4": 4, "mixed": 8}[precision]
    return vmem_bytes(block_m, block_n, block_k, item, w_bits=w_bits)


class SwappedSequential:
    """Generic swapped executor over an arbitrary unit list (used by the
    scenario benchmarks for the paper's conv workloads)."""

    def __init__(self, named_units, apply_fn, workdir: str,
                 mode: str = "snet", budget: Optional[int] = None,
                 gpu_dispatch: bool = False, prefetch_depth: int = 2,
                 ledger: Optional[MemoryLedger] = None,
                 cache: Optional[BlockCache] = None,
                 store_backend: Optional[str] = None,
                 precision: str = "int8", fused: bool = False,
                 store_options: Optional[dict] = None):
        """named_units: [(name, params)]; apply_fn(i, params, x) -> x.

        ``precision``/``fused`` apply to the quant backend only: fused=True
        hands apply_fn QuantizedTensor weight leaves (stream through the
        fused dequant-matmul via layers.linear, or materialize at use), so
        apply_fn must be quantization-aware (vision.apply_layer is).
        ``store_options`` overlays extra backend build options on top of the
        derived ones (e.g. ``inner``/``p``/``seed`` for the faulty arm)."""
        self.named_units = list(named_units)
        self.apply_fn = apply_fn
        self.prefetch_depth = max(prefetch_depth, 1)
        self.store_backend = resolve_backend(store_backend, mode)
        self.precision = precision if self.store_backend == "quant" else "fp"
        self.fused = fused and self.store_backend == "quant"
        opts = store_opts(self.store_backend, gpu_dispatch, precision, fused)
        opts.update(store_options or {})
        if self.precision == "mixed" and opts.get("plan") is None:
            raise ValueError("precision='mixed' needs a calibration plan: "
                             "pass store_options={'plan': ...} "
                             "(see repro.calibrate.calibrate_sequential)")
        self.store = build_store(self.named_units, workdir,
                                 backend=self.store_backend, **opts)
        self.engine = SwapEngine(self.store, mode=mode, budget=budget,
                                 gpu_dispatch=gpu_dispatch,
                                 ledger=ledger, cache=cache)
        # the eager quant arm dequantizes BEFORE the matmul, so its kernel
        # streams fp tiles: only the fused path earns the shrunken figure
        self.engine.vmem_working_set = kernel_vmem_working_set(
            self.precision if self.fused else "fp", "float32")
        self.plan: Optional[BlockPlan] = None
        self._block_fns: Dict[Tuple[int, int], Any] = {}
        # calibration seam (repro/calibrate): fn(global_unit_index, params)
        # -> params, applied on host after swap-in, before the jitted block
        # fn — lets the sensitivity profiler substitute one unit's weights
        # per pass while riding the production swap pipeline
        self.param_override: Optional[Any] = None

    def _block_fn(self, lo: int, hi: int):
        """One jitted function per block (layers lo..hi fused): block
        granularity is the execution unit, matching how the paper compiles
        each block into an executable object."""
        key = (lo, hi)
        if key not in self._block_fns:
            def fn(params_list, x, _lo=lo, _hi=hi):
                for off in range(_hi - _lo):
                    x = self.apply_fn(_lo + off, params_list[off], x)
                return x
            self._block_fns[key] = jax.jit(fn)
        return self._block_fns[key]

    def partition_with(self, infos, budget: int, dm: DelayModel,
                       delta: float = 0.05) -> BlockPlan:
        # plan against RESIDENT unit costs: quantized swap units shrink the
        # working set the budget must hold (rows align 1:1 with the units)
        infos = resident_infos(infos, self.engine.store,
                               [n for n, _ in self.named_units])
        planner = PartitionPlanner(infos, dm, m=self.prefetch_depth)
        self.plan, self.table = planner.best_partition(budget, delta)
        self.planner = planner
        return self.plan

    def set_plan(self, points) -> None:
        self.plan = BlockPlan(tuple(points), len(self.named_units),
                              m=self.prefetch_depth)

    def forward(self, x) -> Tuple[Any, Dict]:
        assert self.plan is not None
        eng = self.engine
        names = [n for n, _ in self.named_units]
        t_start = time.perf_counter()
        for bi, lo, hi, handle in swap_schedule(eng, self.plan.blocks(),
                                                names, self.plan.m):
            with span("exec", block=bi):
                t0 = time.perf_counter()
                ps = handle.params
                if self.param_override is not None:
                    ps = [self.param_override(lo + off, p)
                          for off, p in enumerate(ps)]
                x = self._block_fn(lo, hi)(ps, x)
                with span("exec.sync"):
                    x = jax.block_until_ready(x)
                eng.record_exec(time.perf_counter() - t0)
        total = time.perf_counter() - t_start
        st = eng.stats
        return x, {"latency_s": total,
                   "peak_resident_mb": st.peak_resident / 1e6,
                   "t_in": list(st.t_in), "t_ex": list(st.t_ex),
                   "t_out": list(st.t_out),
                   "overlap_efficiency": st.overlap_efficiency(),
                   "cache_hit_rate": st.cache_hit_rate(),
                   "store_backend": self.store_backend,
                   "precision": self.precision,
                   "bytes_swapped": st.bytes_swapped,
                   "bytes_logical": st.bytes_logical,
                   "bytes_resident_quantized": st.bytes_resident_quantized,
                   "bytes_by_precision": dict(st.bytes_by_precision),
                   "vmem_working_set": st.vmem_working_set,
                   "retries": st.retries, "faults": dict(st.faults)}

    def close(self):
        self.engine.close()


class SwappedModel:
    """Executes ``model.prefill``-equivalent inference by swapping blocks."""

    def __init__(self, model: Model, params: dict, workdir: str,
                 mode: str = "snet", budget: Optional[int] = None,
                 gpu_dispatch: bool = False, prefetch_depth: int = 2,
                 ledger: Optional[MemoryLedger] = None,
                 cache: Optional[BlockCache] = None,
                 name: Optional[str] = None,
                 store_backend: Optional[str] = None,
                 precision: Optional[str] = None,
                 store_options: Optional[dict] = None):
        self.model = model
        self.cfg = model.cfg
        self.name = name or model.cfg.name
        self.prefetch_depth = max(prefetch_depth, 1)
        self.store_backend = resolve_backend(store_backend, mode)
        if self.store_backend == "quant" and not self.cfg.quant_eligible:
            # per-model eligibility knob (configs): architectures whose
            # dynamics amplify weight error serve from the exact store
            self.store_backend = "mmap"
        # precision axis: fp for exact stores; else the caller's override or
        # the config's per-model swap precision (int8 | int4). Quant units
        # stay quantized-RESIDENT (no eager dequant): 2-D MLP/head weights
        # stream through the fused dequant-matmul, the rest dequantize at
        # use (see kernels/qtensor.cast_unit_params).
        if self.store_backend == "quant":
            self.precision = precision or self.cfg.swap_precision
        else:
            self.precision = "fp"
        self.units = split_units(model, params)
        prefix = f"{name}/" if name else ""
        for u in self.units:            # namespace units per model so a
            u.name = prefix + u.name    # shared cache/store never collides
        pinned = tuple({u.name for u in self.units if u.kind == "shared_attn"})
        # de-dup shared units in the store
        seen, store_units = set(), []
        for u in self.units:
            if u.name in seen:
                continue
            seen.add(u.name)
            store_units.append((u.name, u.params))
        opts = store_opts(self.store_backend, gpu_dispatch,
                          self.precision, fused=True)
        opts.update(store_options or {})
        if self.precision == "mixed" and opts.get("plan") is None:
            # a mixed store without a plan would silently store EVERY unit
            # raw; the calibration pass must run first (multi_model and
            # serve.py do this automatically)
            raise ValueError("precision='mixed' needs a calibration plan: "
                             "pass store_options={'plan': ...} "
                             "(see repro.calibrate.calibrate_model)")
        self.store = build_store(store_units, workdir,
                                 backend=self.store_backend, **opts)
        self.engine = SwapEngine(self.store, mode=mode, budget=budget,
                                 gpu_dispatch=gpu_dispatch, pinned=pinned,
                                 ledger=ledger, cache=cache)
        self.engine.vmem_working_set = kernel_vmem_working_set(
            self.precision, self.cfg.dtype)
        self.plan: Optional[BlockPlan] = None
        self._jitted: Dict[str, Any] = {}
        # calibration seam (repro/calibrate): fn(Unit, params) -> params,
        # applied after swap-in inside forward_partial's unit loop
        self.param_override: Optional[Any] = None

    # ------------------------------------------------------------ partition
    def partition(self, budget: int, dm: DelayModel, batch: int, seq: int,
                  delta: float = 0.05) -> BlockPlan:
        infos = unit_infos(self.model, self.units, batch, seq)
        # block-plan search sees the RESIDENT working set: quantized units
        # cost their payload, so the same budget packs more layers per block
        infos = resident_infos(infos, self.engine.store,
                               [u.name for u in self.units])
        planner = PartitionPlanner(infos, dm, m=self.prefetch_depth)
        self.plan, self.table = planner.best_partition(budget, delta)
        self.planner = planner
        return self.plan

    def set_plan(self, points: Tuple[int, ...]) -> None:
        self.plan = BlockPlan(tuple(points), len(self.units),
                              m=self.prefetch_depth)

    # ------------------------------------------------------------ apply fns
    def _head_logits(self, uparams: dict, h):
        """Final-norm + lm_head projection as one compiled program, like
        the layers (:func:`head_logits`)."""
        if uparams.get("lm_head") is None:
            raise ValueError("tied head needs the embed unit resident; "
                             "SwappedModel stores lm_head explicitly")
        return head_logits(self.cfg, uparams["final_norm"],
                           uparams["lm_head"], h)

    def _apply_unit(self, unit: Unit, uparams: dict, x, positions, batch,
                    collect: Optional[dict] = None):
        cfg = self.cfg
        if unit.kind == "embed":
            # embeddings are gather/frontend consumers: dequantize at use
            x, positions = self.model._embed(
                materialize_tree(uparams), batch, "prefill")
            return x, positions
        if unit.kind == "head":
            # only the last position's logits leave a prefill (as in
            # Model.prefill): the head never materializes [B, S, vocab]
            return self._head_logits(uparams, x[:, -1:]), positions
        kind = "dense" if unit.kind == "shared_attn" else unit.kind
        is_local = cfg.is_local_layer(unit.layer_id)
        p = cast_unit_params(uparams, jnp.dtype(cfg.dtype))
        x, new_cache, _ = apply_layer_jit(cfg, kind, p, x, positions,
                                          is_local, None, None, "prefill")
        if collect is not None and unit.layer_id is not None:
            # prefill cache (e.g. the prompt's K/V) captured per layer so a
            # serving admit can seed the paged pool without a second pass
            collect[unit.layer_id] = new_cache
        return x, positions

    # ------------------------------------------------------------ decode
    def _unit_cache_struct(self, unit: Unit, batch: int, max_len: int):
        """Decode cache ShapeDtypeStructs for one layer unit."""
        import jax.numpy as jnp
        from repro.models import ssm as ssm_mod
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kind = "dense" if unit.kind == "shared_attn" else unit.kind
        B, L = batch, max_len
        if kind == "mamba2":
            d_inner, nh, ds = ssm_mod.mamba2_dims(cfg)
            return {"h": jnp.zeros((B, nh, cfg.ssm.head_dim, ds), jnp.float32),
                    "conv": jnp.zeros((B, cfg.ssm.d_conv - 1, d_inner + 2 * ds), dt)}
        if kind == "rwkv6":
            nh, rhd = ssm_mod.rwkv6_dims(cfg)
            return {"S": jnp.zeros((B, nh, rhd, rhd), jnp.float32),
                    "shift1": jnp.zeros((B, 1, cfg.d_model), dt),
                    "shift2": jnp.zeros((B, 1, cfg.d_model), dt)}
        if cfg.mla is not None:
            m = cfg.mla
            return {"c_kv": jnp.zeros((B, L, m.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((B, L, m.qk_rope_head_dim), dt)}
        KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        return {"k": jnp.zeros((B, L, KV, hd), dt),
                "v": jnp.zeros((B, L, KV, hd), dt)}

    def decode_loop(self, prompt_tokens, max_new_tokens: int = 8,
                    max_len: int = 128) -> Tuple[Any, Dict]:
        """Greedy generation with WEIGHT-BLOCK STREAMING (paper §10: LLMs on
        edge AI devices): every decode step swaps the model's blocks through
        the memory window with the m=2 pipeline; only the KV/state caches and
        one or two weight blocks are resident at any time.

        prompt_tokens: [B, S] int32. Returns (generated [B, max_new], stats).
        """
        assert self.plan is not None and self.cfg.supports_decode()
        cfg = self.cfg
        B, S = prompt_tokens.shape
        caches = {i: self._unit_cache_struct(u, B, max_len)
                  for i, u in enumerate(self.units) if u.layer_id is not None}

        unit_names = [u.name for u in self.units]

        def run_tokens(tokens, pos0):
            """Teacher-forced pass, one token at a time, swapped."""
            eng = self.engine
            blocks = self.plan.blocks()
            last_logits = None
            for t in range(tokens.shape[1]):
                tok = tokens[:, t:t + 1]
                pos = jnp.full((B,), pos0 + t, jnp.int32)
                batch = {"token": tok, "pos": pos}
                if cfg.rope_type == "mrope":
                    batch["positions"] = jnp.full((B, 1, 3), pos0 + t, jnp.int32)
                x = positions = None
                gen = swap_schedule(eng, blocks, unit_names, self.plan.m)
                try:
                    for bi, lo, hi, handle in gen:
                        for ui, p in zip(range(lo, hi), handle.params):
                            unit = self.units[ui]
                            if unit.kind == "embed":
                                x, positions = self.model._embed(
                                    materialize_tree(p), batch, "decode")
                            elif unit.kind == "head":
                                last_logits = self._head_logits(p, x)
                            else:
                                kind = "dense" if unit.kind == "shared_attn" else unit.kind
                                pc = cast_unit_params(p, jnp.dtype(cfg.dtype))
                                x, caches[ui], _ = apply_layer_jit(
                                    cfg, kind, pc, x, positions,
                                    cfg.is_local_layer(unit.layer_id),
                                    caches[ui], pos, "decode")
                finally:
                    # a raising step body must drain in-flight prefetches
                    # NOW (ledger bytes, cache leases), not at gc time
                    gen.close()
            return last_logits

        t0 = time.time()
        logits = run_tokens(prompt_tokens, 0)
        out = []
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        for step in range(max_new_tokens):
            out.append(tok)
            if S + step + 1 >= max_len or step == max_new_tokens - 1:
                break
            logits = run_tokens(tok, S + step)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        gen = jnp.concatenate(out, axis=1)
        return gen, {"wall_s": time.time() - t0,
                     "peak_resident_mb": self.engine.stats.peak_resident / 1e6}

    def decode_step_paged(self, batch: dict, view) -> jax.Array:
        """One BATCHED decode step through the paged KV cache (continuous
        batching, serving/batch_engine.py): the model's weight blocks stream
        through the memory window exactly ONCE and their swap-in cost
        amortizes over every active sequence — the step cost is
        ~(swap time) + B * (per-token compute) instead of B * (swap time) as
        with per-sequence decode_loop calls. Attention K/V land in the page
        pool via ``view`` (serving/paged_kv.PagedBatchView), so there is no
        contiguous per-batch cache and batch membership may change freely
        between steps.

        batch: ``{"token": [B, 1], "pos": [B]}`` (+ ``"positions"`` for
        mrope). Returns last-position logits [B, 1, vocab].
        """
        assert self.plan is not None and self.cfg.supports_decode()
        cfg = self.cfg
        eng = self.engine
        names = [u.name for u in self.units]
        x = positions = logits = None
        with span("decode_step", step=view.step):
            gen = swap_schedule(eng, self.plan.blocks(), names, self.plan.m)
            try:
                for bi, lo, hi, handle in gen:
                    with span("exec", block=bi):
                        t0 = time.perf_counter()
                        for ui, p in zip(range(lo, hi), handle.params):
                            unit = self.units[ui]
                            if unit.kind == "embed":
                                x, positions = self.model._embed(
                                    materialize_tree(p), batch, "decode")
                            elif unit.kind == "head":
                                logits = self._head_logits(p, x)
                            else:
                                kind = ("dense" if unit.kind == "shared_attn"
                                        else unit.kind)
                                pc = cast_unit_params(p, jnp.dtype(cfg.dtype))
                                x = apply_layer_paged(
                                    cfg, kind, pc, x, positions,
                                    cfg.is_local_layer(unit.layer_id),
                                    view.bind(unit.layer_id))
                        with span("exec.sync"):
                            x = jax.block_until_ready(x)
                        eng.record_exec(time.perf_counter() - t0)
            finally:
                # a raising step body must drain in-flight prefetches NOW
                # (ledger bytes, cache leases), not at gc time
                gen.close()
        return logits

    # ------------------------------------------------------------ forward
    def forward_partial(self, batch: dict, state: Optional[PassState] = None,
                        should_yield=None, collect_cache: bool = False
                        ) -> Tuple[PassState, Optional[Dict]]:
        """Swapped forward pass with block-boundary yield points.

        Runs blocks from ``state`` (fresh pass when None). After each block
        completes (and its handle is swapped out), ``should_yield(state)``
        decides whether to pause: on True the pass returns ``(state, None)``
        with in-flight prefetches drained and only cache-resident bytes still
        charged — the serving scheduler requeues the request and the executor
        is free for higher-urgency work. Resuming re-executes nothing, so a
        preempted pass stays bit-identical to an uninterrupted one.

        Returns ``(state, stats)`` with ``stats`` None while the pass is
        unfinished; on completion ``state.logits`` holds the last-position
        logits and ``stats`` matches :meth:`forward`.
        """
        assert self.plan is not None, "call partition()/set_plan() first"
        eng = self.engine
        names = [u.name for u in self.units]
        if state is None:
            state = PassState(blocks=self.plan.blocks(), m=self.plan.m,
                              caches={} if collect_cache else None)

        t_start = time.perf_counter()
        pending = state.blocks[state.next_block:]
        with span("prefill"):
            gen = swap_schedule(eng, pending, names, state.m)
            try:
                for bi, lo, hi, handle in gen:
                    with span("exec", block=state.next_block):
                        t0 = time.perf_counter()
                        for u, p in zip(self.units[lo:hi], handle.params):
                            if self.param_override is not None:
                                p = self.param_override(u, p)
                            state.x, state.positions = self._apply_unit(
                                u, p, state.x, state.positions, batch,
                                collect=state.caches)
                        with span("exec.sync"):
                            state.x = jax.block_until_ready(state.x)
                        eng.record_exec(time.perf_counter() - t0)
                    state.next_block += 1
                    if (should_yield is not None and not state.done
                            and should_yield(state)):
                        state.preemptions += 1
                        break
            finally:
                gen.close()     # drains in-flight prefetches on early exit
        state.t_active += time.perf_counter() - t_start
        if not state.done:
            return state, None
        state.logits = state.x
        st = eng.stats
        return state, {
            "latency_s": state.t_active,
            "preemptions": state.preemptions,
            "t_in": list(st.t_in), "t_ex": list(st.t_ex), "t_out": list(st.t_out),
            "peak_resident_mb": st.peak_resident / 1e6,
            "meta_mb": self.store.meta_bytes() / 1e6,
            "overlap_efficiency": st.overlap_efficiency(),
            "cache_hit_rate": st.cache_hit_rate(),
            "store_backend": self.store_backend,
            "precision": self.precision,
            "bytes_swapped": st.bytes_swapped,
            "bytes_logical": st.bytes_logical,
            "bytes_resident_quantized": st.bytes_resident_quantized,
            "bytes_by_precision": dict(st.bytes_by_precision),
            "vmem_working_set": st.vmem_working_set,
            "retries": st.retries, "faults": dict(st.faults),
        }

    def forward(self, batch: dict) -> Tuple[jax.Array, Dict]:
        """Swapped forward pass. Returns (last-position logits, stats)."""
        state, stats = self.forward_partial(batch)
        return state.logits, stats

    def close(self):
        self.engine.close()

"""The system under test, driven the way its users drive it.

``System`` builds the program's serving stack through its own entry
points (``MultiModelRuntime`` -> store, plan; ``ServingScheduler.
from_config``) from a configuration file's ``deployment`` block, with the
benchmark's weights. ``Recorder`` wraps the calls into the layers below
the scheduler (the swapped prefill pass, the paged decode step) with host
spans, named ``bench.<layer>`` in the profiler's trace too. ``warm`` runs
every shape a mix can send before the window, and ``drive`` runs the
window: clients send requests through ``submit_generate`` or ``submit``
and the times of every token and answer are recorded.

This is the only module that imports the program.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                                  # noqa: E402
import jax.numpy as jnp                     # noqa: E402

import traffic                              # noqa: E402
import family                               # noqa: E402
from reference import stored_bytes          # noqa: E402


# ------------------------------------------------------------ compiles
class CompileCounter:
    """Counts programs lowered (compiled, or fetched from the persistent
    cache) while ``on`` is set."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


# ------------------------------------------------------------ spans
class Recorder:
    """Host spans around calls into a layer: (name, start, end, info) on
    ``time.perf_counter``, and the same span as a profiler annotation."""

    def __init__(self):
        self.spans: List[tuple] = []
        self._lock = threading.Lock()

    def wrap(self, name: str, fn: Callable,
             info: Optional[Callable] = None) -> Callable:
        def wrapped(*args, **kw):
            extra = info(*args, **kw) if info is not None else None
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kw)
            t1 = time.perf_counter()
            with self._lock:
                self.spans.append((name, t0, t1, extra))
            return out
        return wrapped


class TimedTokens(list):
    """A request's output list that notes when each token was appended."""

    def __init__(self, on_token: Optional[Callable] = None):
        super().__init__()
        self.times: List[float] = []
        self._on_token = on_token

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)
        if self._on_token is not None:
            self._on_token()


# ------------------------------------------------------------ system
def program_config(cfg: dict, fam):
    """The program's registered configuration of ``cfg["arch"]`` with the
    file's ``program`` cut applied (``family.py``)."""
    from repro.configs import get_arch
    return family.replace(get_arch(cfg["arch"]), family.cut(cfg, fam))


def check_model(model: dict, mc, fam) -> None:
    """The configuration file states the model as the program runs it, key
    by key of the family's ``MODEL_FIELDS``."""
    for key, attr in fam.MODEL_FIELDS.items():
        want = model.get(key)
        got = family.program_value(mc, attr)
        same = (got == want if not isinstance(want, float)
                else abs(float(got) - want) <= 1e-12 * abs(want))
        if not same:
            raise ValueError(f"{mc.name}: {key} is {got!r} in the program, "
                             f"{want!r} in the configuration file")


@dataclass
class System:
    cfg: dict
    family: Any                 # the configuration's family module
    params: Any
    workdir: str
    wrap: Dict[str, Callable] = field(default_factory=dict)

    def __post_init__(self):
        from repro.config import resolve_config
        from repro.core.multi_model import MultiModelRuntime
        from repro.core.serving_scheduler import ServingScheduler
        from repro.models.transformer import Model
        cfg = self.cfg
        self.arch = cfg["arch"]
        mc = program_config(cfg, self.family)
        check_model(cfg["model"], mc, self.family)
        if mc.dtype != cfg["dtype"]:
            raise ValueError(f"{self.arch}: dtype {mc.dtype} != {cfg['dtype']}")
        self.model_cfg = mc
        self.d = self.family.dims(cfg["model"])
        dep = cfg["deployment"]
        self.stored = stored_bytes(self.params, self.d["tied"])
        self.budget = int(self.stored / cfg["budget_ratio"])
        overlay = {"arch": self.arch, "models": [], "reduce": "full",
                   "workload": {"requests": dep["plan"]["batch"],
                                "prompt_len": dep["plan"]["seq"],
                                "priorities": [1.0]},
                   "runtime": dict(dep["runtime"],
                                   budget_mb=self.budget / 1e6)}
        self.serve_cfg = resolve_config(profile=dep["profile"], env={},
                                        cli=overlay)
        t0 = time.perf_counter()
        self.rt = MultiModelRuntime.from_config(self.serve_cfg)
        model = Model(mc)
        self.rt.add_model(self.arch, model, self.params, self.workdir)
        self.params = None          # the harness keeps no device weights
        self.rt.plan(batch=dep["plan"]["batch"], seq=dep["plan"]["seq"])
        self.build_s = time.perf_counter() - t0
        self.sm = self.rt.models[self.arch]
        st = self.sm.store
        self.stored_in_store = sum(st.stored_nbytes(n) for n in st.order)
        self.rec = Recorder()
        sm = self.sm
        sm.forward_partial = self.rec.wrap(
            "bench.prefill", self.wrap.get("prefill", lambda f: f)(
                sm.forward_partial),
            info=lambda batch, **kw: int(np.prod(batch["tokens"].shape)))
        if self.rt.kv_reserve() > 0:
            self.be = self.rt.batch_engine(self.arch)
            sm.decode_step_paged = self.rec.wrap(
                "bench.decode_step", self.wrap.get("decode", lambda f: f)(
                    sm.decode_step_paged),
                info=lambda batch, view: [view.kv.seq_len(r)
                                          for r in view.seq_ids])
        else:
            self.be = None
        self.sched = ServingScheduler.from_config(self.rt, self.serve_cfg)

    # -------------------------------------------------------- counters
    def counters(self) -> dict:
        st = self.sm.engine.stats
        out = {"bytes_swapped": st.bytes_swapped,
               "timeline_len": len(st.timeline),
               "preemptions": self.sched.preemptions}
        if self.be is not None:
            out.update({f"engine.{k}": v for k, v in self.be.stats().items()})
        return out

    def timeline(self) -> List[tuple]:
        return list(self.sm.engine.stats.timeline)

    def close(self) -> None:
        self.sched.shutdown()
        self.rt.close()
        self.be = self.sched = self.rt = self.sm = None
        gc.collect()


# ------------------------------------------------------------ warm-up
def _prompt(n: int) -> List[int]:
    return [1] * n


def warm(system: System, mix: dict) -> dict:
    """Run every shape the mix can send, through the serving path itself
    where a shape's first call is cheap, and the paged-attention kernel
    alone for each (batch, pages) pair past that."""
    sizes = traffic.block_sizes(mix)
    prompts = sorted({p for p, _ in sizes})
    t0 = time.perf_counter()
    if mix["requests"] == "prefill":
        for n in prompts:
            system.sched.submit(system.arch, {"tokens": jnp.asarray(
                [_prompt(n)], jnp.int32)}, priority=1.0).wait(timeout=900)
        return {"warm_s": time.perf_counter() - t0, "shapes": len(prompts)}
    from repro.serving.engine import Request
    reqs = [system.sched.submit_generate(
        system.arch, Request(10**9 + i, _prompt(n), max_new_tokens=1),
        priority=1.0) for i, n in enumerate(prompts)]
    for r in reqs:
        r.wait(timeout=900)
    t_prefill = time.perf_counter() - t0
    n_kernel = _warm_decode(system, mix)
    return {"warm_s": time.perf_counter() - t0, "prefill_s": t_prefill,
            "shapes": len(prompts), "kernel_shapes": n_kernel}


def _warm_decode(system: System, mix: dict) -> int:
    """One real decode step for every batch size, then the kernel for
    every (batch, pages) pair the mix's contexts reach: the first
    ``attend`` call of the step, replayed with its own arguments, whatever
    the hook's signature."""
    from repro.serving.paged_kv import PagedBatchView
    kv, sm = system.be.kv, system.sm
    T = kv.page_tokens
    sizes = traffic.block_sizes(mix)
    lo = -(-(min(p for p, _ in sizes) + 1) // T)
    hi = -(-traffic.max_context(mix) // T)
    clients = mix["arrivals"].get("clients", system.be.max_batch)
    B_max = min(system.be.max_batch, max(clients, 1)) \
        if mix["arrivals"]["process"] == "closed" else system.be.max_batch
    n = 0
    for B in range(1, B_max + 1):
        rids = [("warm", B, b) for b in range(B)]
        for r in rids:
            assert kv.alloc(r, T + 1), "no pages to warm the decode step"
        view = PagedBatchView(kv, rids)
        captured = {}
        attend = view.attend

        def capture(layer, *args, _attend=attend, _c=captured, **kw):
            if not _c:
                _c.update(layer=layer, args=args, kw=kw)
            return _attend(layer, *args, **kw)
        view.attend = capture
        pos = np.asarray([kv.seq_len(r) - 1 for r in rids], np.int32)
        batch = {"token": jnp.asarray([[1] for _ in rids], jnp.int32),
                 "pos": jnp.asarray(pos)}
        np.argmax(np.asarray(sm.decode_step_paged(batch, view))[:, -1], -1)
        for r in rids:
            kv.free(r)
        for pages in range(lo, hi + 1):
            lens = [pages * T] + [1] * (B - 1)
            if sum(-(-x // T) for x in lens) > kv.max_pages:
                continue
            for r, x in zip(rids, lens):
                assert kv.alloc(r, x)
            v = PagedBatchView(kv, rids)
            out = v.attend(captured["layer"], *captured["args"],
                           **captured["kw"])
            jax.block_until_ready(out)
            n += 1
            for r in rids:
                kv.free(r)
    return n


# ------------------------------------------------------------ the window
@dataclass
class Sent:
    spec: Any
    t_send: float
    req: Any = None           # the program's Request (generate)
    sreq: Any = None          # the scheduler's ServingRequest
    t_done: Optional[float] = None
    error: Optional[BaseException] = None
    answer: Any = None        # prefill: the logits


@dataclass
class Window:
    t_start: float = 0.0       # load began
    t_open: float = 0.0        # window opened
    t_close: float = 0.0
    sent: List[Sent] = field(default_factory=list)
    late_s: List[float] = field(default_factory=list)   # open loop
    counters_open: dict = field(default_factory=dict)
    counters_close: dict = field(default_factory=dict)


def drive(system: System, mix: dict, seed: int, seconds: float,
          on_open: Callable[[], None] = lambda: None,
          on_close: Callable[[], None] = lambda: None) -> Window:
    """Send the mix's requests for ``seconds`` after the load reaches its
    steady state; close the open requests after the window."""
    from repro.serving.engine import Request
    w = Window()
    specs = traffic.requests(mix, seed, system.d["V"])
    lock = threading.Lock()
    stop = threading.Event()
    ramp = threading.Event()
    ramp_tokens = int(mix.get("ramp_tokens", 0))
    emitted = [0]
    generate = mix["requests"] == "generate"
    if ramp_tokens <= 0:
        ramp.set()

    def on_token():
        emitted[0] += 1
        if emitted[0] >= ramp_tokens:
            ramp.set()

    def send(spec) -> Optional[Sent]:
        with lock:
            if stop.is_set():
                return None
            if generate:
                req = Request(spec.index, spec.prompt,
                              max_new_tokens=spec.max_new_tokens,
                              output=TimedTokens(on_token))
                s = Sent(spec, time.perf_counter(), req=req)
                s.sreq = system.sched.submit_generate(system.arch, req,
                                                      priority=1.0)
            else:
                batch = {"tokens": jnp.asarray([spec.prompt], jnp.int32)}
                s = Sent(spec, time.perf_counter())
                s.sreq = system.sched.submit(system.arch, batch,
                                             priority=1.0)
            w.sent.append(s)
        return s

    def finish(s: Sent) -> None:
        try:
            s.sreq.wait(timeout=600)
            if not generate:
                s.answer = s.sreq.logits
        except Exception as e:          # noqa: BLE001 — counted as failed
            s.error = e
        s.t_done = time.perf_counter()

    def closed_client():
        while not stop.is_set():
            with lock:
                spec = next(specs)
            s = send(spec)
            if s is None:
                return
            finish(s)

    threads: List[threading.Thread] = []
    arrivals = mix["arrivals"]
    w.t_start = time.perf_counter()
    if arrivals["process"] == "closed":
        threads = [threading.Thread(target=closed_client, daemon=True)
                   for _ in range(int(arrivals["clients"]))]
    else:
        def dispatcher():
            waiters = []
            for spec in specs:
                due = w.t_start + spec.due
                now = time.perf_counter()
                if due > now:
                    if stop.wait(due - now):
                        break
                s = send(spec)
                if s is None:
                    break
                s.t_send = due          # latency counts from when it was due
                w.late_s.append(time.perf_counter() - due)
                t = threading.Thread(target=finish, args=(s,), daemon=True)
                t.start()
                waiters.append(t)
            for t in waiters:
                t.join()
        threads = [threading.Thread(target=dispatcher, daemon=True)]
    for t in threads:
        t.start()
    if not ramp.wait(timeout=900):
        raise TimeoutError("the load never reached the window's start")
    w.t_open = time.perf_counter()
    w.counters_open = system.counters()
    on_open()
    time.sleep(max(0.0, w.t_open + seconds - time.perf_counter()))
    w.t_close = time.perf_counter()
    on_close()
    w.counters_close = system.counters()
    with lock:
        stop.set()
        for s in w.sent:        # open requests end at their next token
            if s.req is not None and s.t_done is None:
                s.req.max_new_tokens = 0
    for t in threads:
        t.join(timeout=900)
        if t.is_alive():
            raise TimeoutError("a client did not finish after the window")
    return w

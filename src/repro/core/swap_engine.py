"""Block swapping controller (paper §4): swap-in / swap-out executor.

Storage is a pluggable tier (``repro.store``): the engine asks its
:class:`~repro.store.BlockStore` for each unit and does the bookkeeping —
wall-clock (t_in, t_out, and the stall time the executor spends waiting on
prefetch futures, kept over the last ``SwapStats.HISTORY`` events), actual
storage->host traffic (``SwapStats.bytes_swapped``; quantized backends move
~4x less than the logical unit bytes), and a resident-bytes ledger (peak is
what the paper's Figs. 11-13 report).

The paper's ablation arms (Fig. 15) remain the engine's ``mode`` flag and are
resolved against the store:
  * "snet"      — read the store through its own backend (zero-copy mmap for
                  the default store; quantized+dequant for QuantizedStore);
  * "copy_in"   — w/o-uni-add: reinterpret a raw store through RawIOStore
                  (read() page-cache copy + staging copy + transfer, + the
                  GPU dispatch copy for gpu_dispatch models);
  * "dummy_asm" — w/o-mod-ske: zero-copy I/O but framework-default dummy
                  assembly (per-tensor copies, 2x resident during assembly).

The ledger may be PRIVATE (one model, the seed behaviour) or SHARED across
several engines (the §6.2 multi-DNN scenario: co-resident models under one
budget). Prefetch runs on a single loader thread — one swap-in channel,
matching the paper's pipeline model — at any queue depth m >= 1.

An optional LRU BlockCache keeps hot units (embeddings, shared blocks, small
heads) resident across requests so repeat swap-ins skip the I/O + assembly
path entirely; cached bytes are charged to the shared ledger exactly once,
no matter how many engines or handles reference them.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import (Callable, ClassVar, Deque, Dict, List, Mapping,
                    Optional, Sequence)

from repro.errors import SwapError, SwapIOError, SwapTimeoutError
from repro.store import (BlockStore, LayerStore, MmapStore, QuantizedStore,
                         RawIOStore, as_reader)
from repro.tracing import span

__all__ = ["LayerStore", "MmapStore", "RawIOStore", "QuantizedStore",
           "MemoryLedger", "BlockCache", "size_aware_policy", "BlockHandle",
           "SwapStats", "SwapEngine"]


# ------------------------------------------------------------------ ledger
class MemoryLedger:
    """Resident-bytes accounting, optionally shared by several SwapEngines.

    One ledger == one memory budget: when co-resident models each hold blocks
    (plus the shared block cache), the SUM of their bytes is what must stay
    under budget — per-engine ledgers cannot see each other's residency.
    Thread-safe: loader threads add while executor threads drop; a running
    total keeps every operation O(1) so the lock is held for nanoseconds
    (concurrent executors contend on it at every block boundary).

    Two admission paths:

      * :meth:`add` — immediate: over budget raises ``MemoryError`` (the
        single-tenant semantics: a plan whose blocks don't fit is a
        scheduling bug, fail loudly);
      * :meth:`reserve` — blocking: over budget WAITS until other tenants
        drop bytes, with PRIORITY WAKEUP — when bytes free, the
        highest-priority waiter is admitted first (FIFO within one priority
        class), so a high-urgency request's swap-ins never queue behind a
        batch tenant's. Used by concurrent serving (``executors > 1``).
    """

    def __init__(self, budget: Optional[int] = None):
        self.budget = budget
        self._entries: Dict[object, int] = {}
        self._total = 0
        self._cond = threading.Condition()
        # active reserve() tickets, ordered by (-priority, seq): the minimum
        # ticket is the next waiter allowed to admit (anti-inversion barrier)
        self._waiting: List[tuple] = []
        self._seq = 0
        self.peak = 0

    @property
    def resident(self) -> int:
        with self._cond:
            return self._total

    def _admit_locked(self, key: object, nbytes: int) -> bool:
        """Try to charge under the lock; False if it would exceed budget."""
        delta = nbytes - self._entries.get(key, 0)
        if self.budget is not None and self._total + delta > self.budget:
            return False
        self._entries[key] = nbytes
        self._total += delta
        self.peak = max(self.peak, self._total)
        return True

    def add(self, key: object, nbytes: int, what: str = "block") -> int:
        """Charge ``nbytes``; returns the post-add resident total. Over
        budget: nothing is recorded before raising, so one rejected request
        cannot permanently inflate a ledger other tenants share."""
        with self._cond:
            if self._admit_locked(key, nbytes):
                return self._total
            total = self._total + nbytes
        # The paper treats this as a scheduling bug: blocks must fit b.
        raise MemoryError(
            f"resident {total/1e6:.1f} MB exceeds budget "
            f"{self.budget/1e6:.1f} MB (while adding {what})")

    def try_add(self, key: object, nbytes: int) -> bool:
        """Non-raising add: False (and no charge) if over budget. The cache
        insertion path — under concurrency a transiently full ledger means
        "don't cache this unit", not "kill the request"."""
        with self._cond:
            return self._admit_locked(key, nbytes)

    def reserve(self, key: object, nbytes: int, what: str = "block",
                priority: float = 0.0,
                timeout: Optional[float] = None) -> int:
        """Blocking add: wait until ``nbytes`` fit under the budget.

        Waiters are admitted highest-priority-first (ties FIFO); while a
        higher-priority waiter is pending, later lower-priority arrivals
        queue behind it even if they would fit — admitting them could eat
        the bytes the urgent request is waiting for (priority inversion).
        ``timeout`` bounds the wait (None = forever); on expiry, or when
        ``nbytes`` alone exceed the budget, raises ``MemoryError``.
        """
        if self.budget is not None and nbytes > self.budget:
            raise MemoryError(
                f"{what}: {nbytes/1e6:.1f} MB can never fit budget "
                f"{self.budget/1e6:.1f} MB")
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            self._seq += 1
            ticket = (-float(priority), self._seq)
            self._waiting.append(ticket)
            try:
                while True:
                    if (min(self._waiting) == ticket
                            and self._admit_locked(key, nbytes)):
                        return self._total
                    if deadline is not None:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            raise MemoryError(
                                f"reserve timeout: {nbytes/1e6:.1f} MB for "
                                f"{what} did not fit budget "
                                f"{(self.budget or 0)/1e6:.1f} MB within "
                                f"{timeout:.1f}s "
                                f"(resident {self._total/1e6:.1f} MB)")
                        self._cond.wait(remaining)
                    else:
                        self._cond.wait()
            finally:
                self._waiting.remove(ticket)
                # our departure may unblock the next-best waiter
                self._cond.notify_all()

    def drop(self, key: object) -> None:
        with self._cond:
            nbytes = self._entries.pop(key, None)
            if nbytes is not None:
                self._total -= nbytes
                self._cond.notify_all()


# ------------------------------------------------------------------ cache
def size_aware_policy(unit_sizes: Mapping[str, int],
                      capacity: int) -> Callable[[str, int], bool]:
    """Admission informed by the partition table's per-unit sizes (ROADMAP
    item (d), shipped): admit exactly the units small enough that the whole
    admitted set provably co-fits in ``capacity``.

    The threshold is the largest size s such that EVERY unit of size <= s
    fits in ``capacity`` together (distinct sizes considered ascending,
    whole size-classes at a time: admitting some-but-not-all units of one
    size would let the marginal ones thrash the cyclic block scan and evict
    the genuinely hot small units). Unlike the static ``admit_frac``
    heuristic this adapts to the actual size distribution: a model of many
    small units caches them all, a model of few huge blocks caches none.
    Unknown names fall back to their observed size.
    """
    sizes = sorted(s for s in unit_sizes.values() if s > 0)
    cum, threshold, i = 0, 0, 0
    while i < len(sizes):
        j = i
        while j < len(sizes) and sizes[j] == sizes[i]:
            j += 1
        group = sizes[i] * (j - i)
        if cum + group > capacity:
            break
        cum += group
        threshold = sizes[i]
        i = j

    def policy(name: str, nbytes: int) -> bool:
        size = unit_sizes.get(name, nbytes)
        return 0 < size <= threshold

    return policy


class BlockCache:
    """LRU cache of assembled units, shared across engines and requests.

    Entries are charged to the ledger once under a per-name key — a unit
    shared by two models (or referenced by several in-flight handles) never
    double-counts. Entries pinned via :meth:`pin` are never evicted (the
    seed's ``pinned=`` behaviour); other entries are evicted LRU-first once
    ``capacity`` bytes are exceeded, but only when no handle still references
    them (refcounted, so the ledger never loses sight of live bytes).

    Admission is a pluggable ``policy`` (a ``(name, nbytes) -> bool``
    constructor argument). Default (policy=None) is the thresholded
    heuristic: only units no larger than ``admit_frac`` of capacity enter —
    a block traversal is a cyclic scan, so admit-everything LRU would evict
    each unit just before its next use and hit 0%. :func:`size_aware_policy`
    upgrades this with the partition table's per-unit sizes (installed by
    ``MultiModelRuntime.plan``)."""

    def __init__(self, capacity: int, ledger: MemoryLedger,
                 admit_frac: float = 0.25,
                 policy: Optional[Callable[[str, int], bool]] = None):
        self.capacity = capacity
        self.admit_frac = admit_frac
        self.policy = policy
        self.ledger = ledger
        self._lock = threading.RLock()
        # name -> [params, ledger_bytes, refcount]
        self._entries: "OrderedDict[str, list]" = OrderedDict()
        self._pinned: set = set()
        self.hits = 0
        self.misses = 0

    # ------------------------------------------------------------ policy
    def pin(self, names: Sequence[str]) -> None:
        with self._lock:
            self._pinned.update(names)

    @property
    def pinned(self) -> frozenset:
        with self._lock:
            return frozenset(self._pinned)

    def set_policy(self,
                   policy: Optional[Callable[[str, int], bool]]) -> None:
        with self._lock:
            self.policy = policy

    def admits(self, name: str, nbytes: int) -> bool:
        """Pinned units always enter; others go through the admission policy
        (per-unit-size aware when installed, else the admit_frac heuristic).
        ``nbytes`` is the unit's RESIDENT cost when cached (stored bytes for
        quantized backends)."""
        with self._lock:
            if name in self._pinned:
                return True
            if self.policy is not None:
                return self.policy(name, nbytes)
            return 0 < nbytes <= self.capacity * self.admit_frac

    # ------------------------------------------------------------ lookup
    def acquire(self, name: str, count: bool = True):
        """Return cached params (bumping LRU + refcount) or None."""
        with self._lock:
            e = self._entries.get(name)
            if e is None:
                if count:
                    self.misses += 1
                return None
            self._entries.move_to_end(name)
            e[2] += 1
            if count:
                self.hits += 1
            return e[0]

    def release(self, name: str) -> None:
        with self._lock:
            e = self._entries.get(name)
            if e is not None:
                e[2] = max(e[2] - 1, 0)

    def put(self, name: str, params, ledger_bytes: int) -> bool:
        """Insert (idempotent) and evict LRU unpinned idle entries to fit.
        Returns whether the unit is cache-resident afterwards: a transiently
        full shared ledger declines the insert (False) instead of raising —
        under concurrency "can't cache right now" must not kill the request
        (the caller charges its own handle instead)."""
        with self._lock:
            if name in self._entries:
                return True
            # charge first: if the ledger declines (budget), nothing inserted
            if not self.ledger.try_add(("cache", name), ledger_bytes):
                return False
            self._entries[name] = [params, ledger_bytes, 0]
            self._evict_to_capacity()
            return name in self._entries

    def _evict_to_capacity(self) -> None:
        over = self._unpinned_bytes() - self.capacity
        if over <= 0:
            return
        for name in list(self._entries):
            if over <= 0:
                break
            e = self._entries[name]
            if name in self._pinned or e[2] > 0:
                continue
            over -= e[1]
            del self._entries[name]
            self.ledger.drop(("cache", name))

    def _unpinned_bytes(self) -> int:
        return sum(e[1] for n, e in self._entries.items()
                   if n not in self._pinned)

    # ------------------------------------------------------------ stats
    def active_leases(self) -> Dict[str, int]:
        """Entries some in-flight handle still references (name ->
        refcount). Outside a pass this must be EMPTY — a non-zero refcount
        with no live handle is a leaked lease that makes the entry
        unevictable forever; the fault-path regression tests assert on it."""
        with self._lock:
            return {n: e[2] for n, e in self._entries.items() if e[2] > 0}

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e[1] for e in self._entries.values())

    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def clear(self) -> None:
        with self._lock:
            for name in list(self._entries):
                self.ledger.drop(("cache", name))
            self._entries.clear()


# ------------------------------------------------------------------ handles
@dataclass
class BlockHandle:
    names: List[str]
    params: List[dict]           # assembled (by reference) param trees
    nbytes: int                  # logical (dequantized) block bytes
    resident_bytes: int          # ledger bytes incl. mode-induced extra copies
    io_s: float = 0.0
    asm_s: float = 0.0
    cached_names: List[str] = field(default_factory=list)


def _ring() -> deque:
    return deque(maxlen=SwapStats.HISTORY)


@dataclass
class SwapStats:
    """Wall-clock + byte accounting of one engine. The three byte currencies
    the ledger report distinguishes:

      * ``bytes_logical``            — LOGICAL (dequantized) bytes the
                                       swap-ins delivered;
      * ``bytes_swapped``            — STREAMED: actual storage->host I/O
                                       traffic (quantized backends move
                                       4-8x less than logical);
      * ``bytes_resident_quantized`` — RESIDENT-quantized: payload bytes
                                       delivered still in quantized form
                                       (``QuantizedTensor`` leaves, the
                                       fused path) — these stay quantized
                                       in device memory and in the VMEM
                                       weight stream.

    ``vmem_working_set`` is the per-kernel figure: bytes the weight-stream
    matmul holds in VMEM at the default tiling for this engine's store
    precision (set by the runtime from ``kernels.swap_linear.vmem_bytes``;
    the fused path shrinks the weight window 2x int8 / 4x int4).

    ``timeline`` is the per-stage event log the overlap analysis runs on:
    ``(stage, start, end)`` tuples in ``time.perf_counter`` absolute
    seconds. Loader-side stages come from each :class:`UnitRead` ("read" =
    storage -> host, "unpack" = dequant/assembly, "dispatch" = host ->
    device incl. the on-device flush); the engine adds executor-side
    events ("wait" = stall on a prefetch future, "exec" = block compute).
    A healthy depth-m pipeline shows block i+1's "read" span INSIDE block
    i's "exec" span — :meth:`overlap_seconds` measures exactly that, so a
    serialization point is attributable to the stage that caused it
    instead of disappearing into an aggregate latency.

    ``timeline`` and the per-event lists (``t_in``, ``t_ex``, ``t_out``,
    ``t_wait``) are rings of the last ``HISTORY`` entries each, so a
    long-lived server holds a bounded log (a decode step adds about 110
    timeline events); sums over them, ``overlap_efficiency`` included,
    cover that recent history. The byte and count fields are totals."""
    HISTORY: ClassVar[int] = 65536
    t_in: Deque[float] = field(default_factory=_ring)
    t_ex: Deque[float] = field(default_factory=_ring)
    t_out: Deque[float] = field(default_factory=_ring)
    t_wait: Deque[float] = field(default_factory=_ring)  # stalls
    timeline: Deque[tuple] = field(default_factory=_ring)
    peak_resident: int = 0
    bytes_swapped: int = 0       # actual storage->host I/O traffic
    bytes_logical: int = 0       # dequantized bytes those swap-ins delivered
    bytes_resident_quantized: int = 0   # delivered still-quantized (fused)
    vmem_working_set: int = 0    # per-kernel VMEM bytes at this precision
    cache_hits: int = 0
    cache_misses: int = 0
    # fault accounting (docs/ARCHITECTURE.md "Failure handling"): ``retries``
    # counts re-read attempts the loader burned recovering; ``faults`` tallies
    # every failed read attempt by taxonomy class (SwapIOError /
    # SwapCorruptionError / SwapTimeoutError) INCLUDING the ones retries
    # absorbed — a healthy-looking pass over flaky storage is visible here.
    # The timeline gains "retry" spans covering each backoff sleep.
    retries: int = 0
    faults: Dict[str, int] = field(default_factory=dict)
    # streamed I/O split by STORED precision ({"fp"|"int8"|"int4": bytes},
    # summing to ``bytes_swapped``): under a mixed-precision plan this is
    # the realized per-precision byte breakdown; uniform stores report one
    # bucket (their precision, "fp" for exact backends).
    bytes_by_precision: Dict[str, int] = field(default_factory=dict)

    # ------------------------------------------------------------ timeline
    def stage_spans(self, stage: str) -> List[tuple]:
        """All ``(start, end)`` spans recorded for ``stage``, in log order."""
        return [(s, e) for st, s, e in self.timeline if st == stage]

    def stage_seconds(self, stage: str) -> float:
        """Total wall-clock spent in ``stage`` across the log."""
        return sum(e - s for _, s, e in
                   (ev for ev in self.timeline if ev[0] == stage))

    def overlap_seconds(self, stage_a: str, stage_b: str) -> float:
        """Wall-clock during which ``stage_a`` and ``stage_b`` ran
        CONCURRENTLY (intersection of their merged span sets) — e.g.
        ``overlap_seconds("read", "exec")`` is the host-read time genuinely
        hidden behind compute, the quantity the fused-path fix targets."""

        def merged(stage):
            spans = sorted(self.stage_spans(stage))
            out: List[List[float]] = []
            for s, e in spans:
                if out and s <= out[-1][1]:
                    out[-1][1] = max(out[-1][1], e)
                else:
                    out.append([s, e])
            return out

        a, b = merged(stage_a), merged(stage_b)
        total, i, j = 0.0, 0, 0
        while i < len(a) and j < len(b):
            lo = max(a[i][0], b[j][0])
            hi = min(a[i][1], b[j][1])
            if hi > lo:
                total += hi - lo
            if a[i][1] < b[j][1]:
                i += 1
            else:
                j += 1
        return total

    def overlap_efficiency(self) -> float:
        """Fraction of swap-in time hidden behind execution, over the last
        ``HISTORY`` swap-ins and stalls: 1.0 means the executor never
        stalled on a prefetch (paper Fig. 10's ideal); 0.0 means every
        swap-in was fully visible (serial)."""
        total_in = sum(self.t_in)
        if total_in <= 0.0:
            return 1.0
        return max(0.0, 1.0 - sum(self.t_wait) / total_in)

    def cache_hit_rate(self) -> float:
        n = self.cache_hits + self.cache_misses
        return self.cache_hits / n if n else 0.0


class SwapEngine:
    """One model's swap-in/swap-out executor over a pluggable BlockStore.

    ``ledger`` and ``cache`` may be shared with other engines (multi-model
    serving under one budget); by default each engine gets a private ledger
    seeded from ``budget`` and a pin-only cache (capacity 0: only ``pinned``
    units are retained, the seed behaviour). ``mode`` selects the paper's
    ablation arms against a raw-format store (see module docstring)."""

    def __init__(self, store: BlockStore, mode: str = "snet",
                 budget: Optional[int] = None, gpu_dispatch: bool = False,
                 pinned: Sequence[str] = (),
                 ledger: Optional[MemoryLedger] = None,
                 cache: Optional[BlockCache] = None):
        assert mode in ("snet", "copy_in", "dummy_asm")
        self.store = as_reader(store, mode=mode, gpu_dispatch=gpu_dispatch)
        self.mode = mode
        self.gpu_dispatch = gpu_dispatch
        self.ledger = ledger if ledger is not None else MemoryLedger(budget)
        self.cache = cache if cache is not None else BlockCache(0, self.ledger)
        self.cache.pin(pinned)
        self.stats = SwapStats()
        # per-kernel VMEM working set of the weight-stream matmul at this
        # store's precision; the runtime sets it (kernels.vmem_bytes) and
        # swap_in republishes it into stats so resets don't lose it
        self.vmem_working_set = 0
        # Concurrent serving knobs (set by MultiModelRuntime when
        # executors > 1): reserve_blocking makes over-budget swap-ins WAIT
        # for other tenants to free bytes (priority wakeup) instead of
        # raising; priority is the urgency of the request currently being
        # served through this engine (per-model passes serialize, so one
        # value per engine suffices); the timeout converts a genuine
        # cross-tenant deadlock into a loud MemoryError.
        self.reserve_blocking = False
        self.reserve_timeout: Optional[float] = 30.0
        self.priority = 0.0
        # Fault-tolerance knobs (docs/ARCHITECTURE.md "Failure handling"):
        # a failed unit read is retried up to ``read_retries`` times with
        # exponential backoff starting at ``retry_backoff_s`` (doubling per
        # attempt); ``read_deadline_s`` bounds ONE read attempt — a read
        # that returns after the deadline is discarded and counted as
        # SwapTimeoutError (retryable), so a storage latency cliff cannot
        # silently become unbounded serving tail latency.
        self.read_retries = 2
        self.retry_backoff_s = 0.01
        self.read_deadline_s: Optional[float] = None
        self._loader = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="swapnet-loader")

    # -------------------------------------------------------------- ledger
    @property
    def pinned(self) -> frozenset:
        """The cache is the single source of truth for pinned-ness (a shared
        cache may pin units for several engines; callers filter by store)."""
        return self.cache.pinned

    @property
    def budget(self) -> Optional[int]:
        return self.ledger.budget

    @property
    def resident_bytes(self) -> int:
        return self.ledger.resident

    def set_priority(self, priority: float) -> None:
        """Urgency of the request this engine is currently serving; swap-ins
        issued on the loader thread inherit it for ledger priority wakeup."""
        self.priority = float(priority)

    def _ledger_add(self, handle: BlockHandle) -> None:
        what = (f"block[{','.join(handle.names[:3])}...]"
                if len(handle.names) > 3
                else f"block[{','.join(handle.names)}]")
        if self.reserve_blocking:
            total = self.ledger.reserve(id(handle), handle.resident_bytes,
                                        what, priority=self.priority,
                                        timeout=self.reserve_timeout)
        else:
            total = self.ledger.add(id(handle), handle.resident_bytes, what)
        # per-engine peak = residency observed while THIS engine was adding;
        # resettable via stats.__init__() (the ledger's .peak is the
        # monotone lifetime number the multi-model stats report).
        self.stats.peak_resident = max(self.stats.peak_resident, total)

    # -------------------------------------------------------------- swap-in
    def _read_with_retry(self, name: str):
        """One unit read through the fault-tolerance tier: normalize store
        exceptions to the SwapError taxonomy, enforce the per-read deadline,
        retry with exponential backoff. Returns the clean ``UnitRead``; what
        escapes the retries carries ``unit``/``attempts`` context for the
        scheduler tier. Runs on the loader thread (like the read itself)."""
        delay = self.retry_backoff_s
        attempt = 0
        while True:
            attempt += 1
            t0 = time.perf_counter()
            try:
                r = self.store.read_unit(name)
            except SwapError as e:
                err = e
            except OSError as e:
                err = SwapIOError(f"unit {name!r}: {e}", unit=name)
                err.__cause__ = e
            else:
                took = time.perf_counter() - t0
                if (self.read_deadline_s is None
                        or took <= self.read_deadline_s):
                    return r
                # late data is failed data: keeping it would let one slow
                # read stretch the pipeline unboundedly — discard and retry
                err = SwapTimeoutError(
                    f"unit {name!r}: read took {took * 1e3:.1f} ms, "
                    f"deadline {self.read_deadline_s * 1e3:.1f} ms",
                    unit=name)
            kind = type(err).__name__
            self.stats.faults[kind] = self.stats.faults.get(kind, 0) + 1
            if attempt > self.read_retries:
                err.attempts = attempt
                raise err
            self.stats.retries += 1
            s0 = time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.stats.timeline.append(("retry", s0, time.perf_counter()))
            delay *= 2

    def swap_in(self, names: Sequence[str]) -> BlockHandle:
        params: List[dict] = []
        cached: List[str] = []
        total, ledger, loaded, io_s, asm_s = 0, 0, 0, 0.0, 0.0
        try:
            for name in names:
                hit = self.cache.acquire(name)
                if hit is not None:
                    params.append(hit)
                    cached.append(name)
                    self.stats.cache_hits += 1
                    continue
                r = self._read_with_retry(name)
                n = self.store.nbytes(name)
                params.append(r.params)
                io_s += r.io_s
                asm_s += r.asm_s
                loaded += r.io_bytes
                self.stats.timeline.extend(r.stages)
                self.stats.bytes_logical += n
                self.stats.bytes_resident_quantized += r.quantized_bytes
                # per-precision I/O split: mixed stores report it per read;
                # single-precision backends bucket the whole read under the
                # store's precision ("fp" for exact ones)
                pb = r.precision_bytes
                if pb is None:
                    pb = {getattr(self.store, "precision", "fp"): r.io_bytes}
                for prec, b in pb.items():
                    if b:
                        self.stats.bytes_by_precision[prec] = \
                            self.stats.bytes_by_precision.get(prec, 0) + b
                self.stats.cache_misses += 1
                # admission reasons in the unit's RESIDENT cost — exactly
                # what the cache entry will charge the ledger (2-3x logical
                # for rawio, the quantized payload for quant): sizing by
                # stored bytes would admit sets that overflow capacity and
                # thrash the cyclic scan to a 0% hit rate.
                if (n and self.cache.admits(name, r.ledger_bytes)
                        and self.cache.put(name, r.params, r.ledger_bytes)):
                    # hot unit: retained across requests, charged to the
                    # ledger once under the cache's key — not this handle's.
                    if self.cache.acquire(name, count=False) is not None:
                        cached.append(name)
                    else:           # raced out by eviction: charge the handle
                        total += n
                        ledger += r.ledger_bytes
                else:
                    total += n
                    ledger += r.ledger_bytes
            handle = BlockHandle(list(names), params, total, ledger,
                                 io_s, asm_s, cached_names=cached)
            self._ledger_add(handle)
        except BaseException:
            # failed partway (I/O error, ledger rejection): no handle will
            # ever be swapped out, so drop the cache leases taken above —
            # a leaked refcount would make those entries unevictable forever.
            for name in cached:
                self.cache.release(name)
            raise
        self.stats.t_in.append(io_s + asm_s)
        self.stats.vmem_working_set = self.vmem_working_set
        self.stats.bytes_swapped += loaded   # actual I/O traffic: cache hits
        return handle                        # skip it, admitted loads count

    def prefetch(self, names: Sequence[str]) -> Future:
        """Pipelined prefetch: the loader thread fetches upcoming blocks while
        the executor runs the current one (paper Fig. 10). A single loader
        thread = one swap-in channel; queue depth is the caller's m-1."""
        return self._loader.submit(self.swap_in, list(names))

    def wait(self, fut: Future) -> BlockHandle:
        """Block on a prefetch future, recording the stall as visible t_in."""
        with span("swap.wait"):
            t0 = time.perf_counter()
            handle = fut.result()
            t1 = time.perf_counter()
        self.stats.t_wait.append(t1 - t0)
        self.stats.timeline.append(("wait", t0, t1))
        return handle

    # -------------------------------------------------------------- swap-out
    def swap_out(self, handle: BlockHandle) -> float:
        """Write-back-free: parameters are immutable — drop references, GC.
        Cache-resident units merely drop their lease. Returns t_out."""
        with span("swap.out"):
            t0 = time.perf_counter()
            handle.params = []
            for name in handle.cached_names:
                self.cache.release(name)
            handle.cached_names = []
            self.ledger.drop(id(handle))
            gc.collect(0)
            dt = time.perf_counter() - t0
        self.stats.t_out.append(dt)
        return dt

    def record_exec(self, seconds: float) -> None:
        """Executor-side compute accounting: called right after a block's
        forward with its wall-clock, so the "exec" timeline span is the
        interval ending now."""
        now = time.perf_counter()
        self.stats.t_ex.append(seconds)
        self.stats.timeline.append(("exec", now - seconds, now))

    def close(self) -> None:
        self._loader.shutdown(wait=True)

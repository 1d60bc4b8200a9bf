"""Paged KV cache: fixed-size token pages charged to the shared MemoryLedger.

The SwapNet idea applied to the KV cache (the PIE/vLLM page-table layout):
instead of one contiguous [B, max_len, KV, hd] allocation per batch slot —
whose padding makes batch size a compile-time memory decision — K/V live in
a shared pool of PAGES of ``page_tokens`` tokens each, and every sequence
owns an ordered page list. A page spans ALL layers (one alloc decision per
``page_tokens`` of context, like PIE's NUM_TOKENS_IN_BLOCK blocks), so

    page_bytes = 2 (K+V) * n_layers * page_tokens * KV * hd * itemsize.

Pages are charged to the same :class:`~repro.core.swap_engine.MemoryLedger`
as weight blocks, under one per-sequence key whose value is re-charged with
delta semantics as the sequence grows — KV pages and weight-block residency
compete under ONE budget, so the planner genuinely trades cache-resident
layers against decode batch size. ``alloc``/``extend`` NEVER block and never
partially commit: a rejection (pool exhausted or ledger over budget) leaves
both the free list and the ledger untouched, and the batch engine answers it
with preemption-by-recomputation (free the youngest sequence's pages,
requeue it; greedy decode recomputes bit-identically).

Each layer's K and V pools are device arrays, the only copy, updated in
place: a compiled scatter under buffer donation (``_kv_append``) writes a
decode step's new rows, or a prefill's whole prompt on admission, so no
K/V crosses to the host and nothing waits on the device between a layer's
programs. The pool capacity is preallocated but the ledger only carries
LOGICALLY allocated pages, mirroring how the weight ledger carries resident
blocks, not the store file.

Counters: ``rows_written`` (token rows appended on the device, summed over
layers), ``host_s`` (seconds dispatching the appends, span
``swapnet.kv.write``, ``repro.tracing``), ``upload_bytes`` (host->device
pool bytes, 0 since the pools never leave the device) and the gauge
``device_pool_bytes`` (the pools' device bytes, which the ledger does not
charge).
"""
from __future__ import annotations

import functools
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.swap_engine import MemoryLedger
from repro.kernels import ops
from repro.tracing import span

__all__ = ["PagedKVCache", "PagedBatchView", "page_bytes_for"]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _kv_append(k_pool, v_pool, rows, k, v):
    """Scatter token rows into one layer's [KV, P, T, hd] pools in place:
    ``rows`` [2, N] holds each row's page id and slot, ``k``/``v`` hold the
    N rows of [KV, hd] under any leading shape ([B, KV, hd] from a decode
    step, [1, S, KV, hd] from a prefill). Rows are distinct: each (page,
    slot) belongs to one token of one sequence."""
    KV, hd = k_pool.shape[0], k_pool.shape[-1]

    def put(pool, x):
        x = x.reshape(-1, KV, hd).swapaxes(0, 1).astype(pool.dtype)
        return pool.at[:, rows[0], rows[1]].set(x, unique_indices=True)
    return put(k_pool, k), put(v_pool, v)


def page_bytes_for(cfg: ModelConfig, page_tokens: int) -> int:
    """Ledger cost of one page: K+V for every layer's slice of the page."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.n_layers * page_tokens
            * cfg.n_kv_heads * cfg.resolved_head_dim * itemsize)


class PagedKVCache:
    """Page-table KV cache for one model, accounted on a shared ledger.

    Thread-safe: the batch engine allocates/frees from its driver thread
    while the scheduler admits new sequences from executor threads.
    """

    def __init__(self, cfg: ModelConfig, ledger: MemoryLedger, *,
                 page_tokens: int = 16, max_pages: int = 64,
                 name: str = "kv"):
        if cfg.mla is not None or any(
                k not in ("dense", "moe") for k in cfg.layer_kinds()):
            raise ValueError(
                f"{cfg.name}: paged KV serving covers uniform GQA/MHA "
                f"attention stacks (MLA and SSM/shift state layers keep the "
                f"contiguous legacy path)")
        self.cfg = cfg
        self.ledger = ledger
        self.page_tokens = int(page_tokens)
        self.max_pages = int(max_pages)
        self.name = name
        self.page_bytes = page_bytes_for(cfg, self.page_tokens)
        KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        # page 0 is a permanently-zero SENTINEL: page tables are padded with
        # it past a sequence's pages, so the kernel's gather always lands on
        # a real (masked) page. KV heads lead ([KV, P, T, hd]): the kernel
        # streams one (page_tokens, hd) tile per (kv head, page)
        shape = (KV, self.max_pages + 1, self.page_tokens, hd)
        self.k_pools = [jnp.zeros(shape, dt) for _ in range(cfg.n_layers)]
        self.v_pools = [jnp.zeros(shape, dt) for _ in range(cfg.n_layers)]
        self._free: List[int] = list(range(self.max_pages, 0, -1))
        self._pages: Dict[object, List[int]] = {}
        self._len: Dict[object, int] = {}
        self._lock = threading.Lock()
        # the pools and these counters are written only by the thread
        # stepping the batch engine (one step at a time), read by anyone
        self.rows_written = 0            # token rows appended, all layers
        self.upload_bytes = 0            # host -> device pool bytes: none
        # seconds dispatching the appends; temporary: the benchmark reads
        # it until its trace reader keeps the swapnet.kv.* spans, whose
        # union is the same number, and then it goes with ``_host``'s timing
        self.host_s = 0.0

    @classmethod
    def for_budget(cls, cfg: ModelConfig, ledger: MemoryLedger,
                   kv_bytes: int, *, page_tokens: int = 16,
                   name: str = "kv") -> "PagedKVCache":
        """Size the pool so its pages exactly fill ``kv_bytes`` when all
        allocated (the ledger still arbitrates: weight blocks can squeeze
        the usable page count below capacity at runtime)."""
        pb = page_bytes_for(cfg, page_tokens)
        max_pages = max(int(kv_bytes) // pb, 1)
        return cls(cfg, ledger, page_tokens=page_tokens, max_pages=max_pages,
                   name=name)

    # ------------------------------------------------------------ pages
    def _pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.page_tokens)

    def _key(self, seq_id) -> tuple:
        return ("kv", self.name, seq_id)

    def alloc(self, seq_id, n_tokens: int) -> bool:
        """Admit a new sequence with ``n_tokens`` of context. False (and no
        state change) if the pool or the ledger cannot take its pages."""
        need = self._pages_for(n_tokens)
        with self._lock:
            assert seq_id not in self._pages, f"sequence {seq_id!r} is live"
            if need > len(self._free):
                return False
            if not self.ledger.try_add(self._key(seq_id),
                                       need * self.page_bytes):
                return False
            self._pages[seq_id] = [self._free.pop() for _ in range(need)]
            self._len[seq_id] = n_tokens
        return True

    def extend(self, seq_id, n_new: int = 1) -> bool:
        """Grow a sequence by ``n_new`` tokens, taking a page at each
        boundary crossing (ledger re-charged with delta semantics). False
        leaves the sequence exactly as it was."""
        with self._lock:
            pages = self._pages[seq_id]
            new_len = self._len[seq_id] + n_new
            need = self._pages_for(new_len) - len(pages)
            if need > 0:
                if need > len(self._free):
                    return False
                if not self.ledger.try_add(
                        self._key(seq_id),
                        (len(pages) + need) * self.page_bytes):
                    return False
                pages.extend(self._free.pop() for _ in range(need))
            self._len[seq_id] = new_len
        return True

    def free(self, seq_id) -> None:
        """Retire a sequence: pages to the free list, ledger released."""
        with self._lock:
            pages = self._pages.pop(seq_id, None)
            if pages is None:
                return
            del self._len[seq_id]
            self._free.extend(reversed(pages))
            self.ledger.drop(self._key(seq_id))

    def seq_len(self, seq_id) -> int:
        with self._lock:
            return self._len[seq_id]

    # ------------------------------------------------------------ host
    @contextmanager
    def _host(self, name: str) -> Iterator[None]:
        """Host time of the KV path: a ``swapnet.<name>`` span, its seconds
        added to ``host_s``."""
        t0 = time.perf_counter()
        with span(name):
            yield
        self.host_s += time.perf_counter() - t0

    # ------------------------------------------------------------ tokens
    def rows(self, seq_id, start: int, n: int) -> jax.Array:
        """[2, n] int32 on the device: the page id and slot of the
        sequence's token positions ``start .. start+n`` (which must be
        allocated), for :meth:`append`."""
        T = self.page_tokens
        pos = np.arange(start, start + n)
        with self._lock:
            assert start + n <= self._len[seq_id], \
                (start, n, self._len[seq_id])
            pids = np.asarray(self._pages[seq_id], np.int32)[pos // T]
        return jnp.asarray(np.stack([pids, (pos % T).astype(np.int32)]))

    def last_rows(self, seq_ids: Sequence) -> jax.Array:
        """[2, B] int32 on the device addressing each sequence's LAST token:
        the decode step's write position, uploaded once and reused by every
        layer's append."""
        T = self.page_tokens
        with self._lock:
            pos = [self._len[s] - 1 for s in seq_ids]
            pids = [self._pages[s][p // T] for s, p in zip(seq_ids, pos)]
        return jnp.asarray(np.asarray([pids, [p % T for p in pos]],
                                      np.int32))

    def append(self, layer: int, rows: jax.Array, k: jax.Array,
               v: jax.Array) -> None:
        """Write token rows ``k``/``v`` ([..., KV, hd], one per column of
        ``rows``) into the layer's pools on the device, in place. Only the
        dispatch runs here; nothing waits for the device."""
        with self._host("kv.write"):
            self.k_pools[layer], self.v_pools[layer] = _kv_append(
                self.k_pools[layer], self.v_pools[layer], rows, k, v)
        self.rows_written += int(rows.shape[1])

    # ------------------------------------------------------------ views
    def page_table(self, seq_ids: Sequence) -> Tuple[np.ndarray, np.ndarray]:
        """(page_table [B, NP] int32 padded with the zero page, seq_lens [B]
        int32) for a batch of live sequences."""
        with self._lock:
            lists = [self._pages[s] for s in seq_ids]
            lens = [self._len[s] for s in seq_ids]
        NP = max((len(p) for p in lists), default=1) or 1
        pt = np.zeros((len(lists), NP), np.int32)
        for i, p in enumerate(lists):
            pt[i, :len(p)] = p
        return pt, np.asarray(lens, np.int32)

    @property
    def device_pool_bytes(self) -> int:
        """Bytes the pools hold on the device (outside the ledger, which
        charges allocated pages only)."""
        return sum(k.nbytes + v.nbytes
                   for k, v in zip(self.k_pools, self.v_pools))

    def counters(self) -> Dict[str, float]:
        """The KV path's counters and the device-pool gauge."""
        return {"rows_written": self.rows_written,
                "upload_bytes": self.upload_bytes,
                "device_pool_bytes": self.device_pool_bytes,
                "host_s": self.host_s}

    # ------------------------------------------------------------ stats
    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return sum(len(p) for p in self._pages.values())

    @property
    def bytes_in_use(self) -> int:
        return self.pages_in_use * self.page_bytes

    def occupancy(self) -> float:
        return self.pages_in_use / max(self.max_pages, 1)

    def live_sequences(self) -> List:
        with self._lock:
            return list(self._pages)


class _LayerBoundView:
    """``PagedBatchView`` narrowed to one layer — the ``paged`` hook that
    ``models.transformer.apply_layer_paged`` attends through."""

    __slots__ = ("_view", "_layer")

    def __init__(self, view: "PagedBatchView", layer: int):
        self._view = view
        self._layer = layer

    def attend(self, q, k_new, v_new, **kw):
        return self._view.attend(self._layer, q, k_new, v_new, **kw)


class PagedBatchView:
    """One decode step's batch, frozen as a page-table snapshot.

    The batch engine extends every active sequence by one token FIRST, then
    builds the view: ``seq_lens`` already counts the token being decoded, so
    each layer's new K/V lands at position ``seq_lens[i] - 1`` and the
    kernel's causal mask (`q_pos = seq_len - 1`) covers exactly the live
    context. The (page_table, seq_lens) device arrays and the append's rows
    are uploaded once and shared by all layers of the step.
    """

    def __init__(self, kv: PagedKVCache, seq_ids: Sequence, step: int = -1):
        self.kv = kv
        self.seq_ids = list(seq_ids)
        self.step = step        # engine step number (-1 outside one), traced
        pt, sl = kv.page_table(self.seq_ids)
        self._rows = kv.last_rows(self.seq_ids)
        self.page_table = jnp.asarray(pt)
        self.seq_lens = jnp.asarray(sl)

    def attend(self, layer: int, q, k_new, v_new, *, scale=None,
               window: Optional[int] = None,
               softcap: Optional[float] = None):
        """Append this layer's new K/V ([B, KV, hd]) to each sequence's
        pages on the device, then attend q ([B, H, hd]) through the page
        table."""
        self.kv.append(layer, self._rows, k_new, v_new)
        return ops.paged_attention(q, self.kv.k_pools[layer],
                                   self.kv.v_pools[layer], self.page_table,
                                   self.seq_lens, scale=scale, window=window,
                                   softcap=softcap)

    def bind(self, layer: int) -> _LayerBoundView:
        return _LayerBoundView(self, layer)

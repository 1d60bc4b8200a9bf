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

Pools are host numpy buffers mutated in place (the decode loop is eager, one
host->device upload per layer per batched step); the pool capacity is
preallocated but the ledger only carries LOGICALLY allocated pages, mirroring
how the weight ledger carries resident blocks, not the store file.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.swap_engine import MemoryLedger
from repro.kernels import ops

__all__ = ["PagedKVCache", "PagedBatchView", "page_bytes_for"]


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
        self.k_pools = [np.zeros(shape, dt) for _ in range(cfg.n_layers)]
        self.v_pools = [np.zeros(shape, dt) for _ in range(cfg.n_layers)]
        self._free: List[int] = list(range(self.max_pages, 0, -1))
        self._pages: Dict[object, List[int]] = {}
        self._len: Dict[object, int] = {}
        self._lock = threading.Lock()
        self._dirty = [True] * cfg.n_layers
        self._dev: List[Optional[Tuple]] = [None] * cfg.n_layers

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

    # ------------------------------------------------------------ tokens
    def write(self, seq_id, layer: int, start: int, k: np.ndarray,
              v: np.ndarray) -> None:
        """Scatter ``k``/``v`` [S, KV, hd] into the sequence's pages at token
        positions ``start .. start+S`` (positions must be allocated)."""
        with self._lock:
            pages = self._pages[seq_id]
            assert start + k.shape[0] <= self._len[seq_id], \
                (start, k.shape, self._len[seq_id])
        T = self.page_tokens
        kp, vp = self.k_pools[layer], self.v_pools[layer]
        t = 0
        while t < k.shape[0]:
            pos = start + t
            pid = pages[pos // T]
            slot = pos % T
            n = min(T - slot, k.shape[0] - t)
            kp[:, pid, slot:slot + n] = k[t:t + n].swapaxes(0, 1)
            vp[:, pid, slot:slot + n] = v[t:t + n].swapaxes(0, 1)
            t += n
        self._dirty[layer] = True

    def last_slots(self, seq_ids: Sequence) -> Tuple[np.ndarray, np.ndarray]:
        """(page_ids [B], slots [B]) addressing each sequence's LAST token —
        the decode-step write position, computed once and reused by every
        layer's batched scatter (``write_rows``)."""
        T = self.page_tokens
        with self._lock:
            pos = [self._len[s] - 1 for s in seq_ids]
            pids = [self._pages[s][p // T] for s, p in zip(seq_ids, pos)]
        return (np.asarray(pids, np.int32),
                np.asarray([p % T for p in pos], np.int32))

    def write_rows(self, layer: int, pids: np.ndarray, slots: np.ndarray,
                   k: np.ndarray, v: np.ndarray) -> None:
        """Scatter one token per sequence ([B, KV, hd]) into pool rows
        addressed by ``last_slots`` — the vectorized decode-step write (one
        fancy-index assignment instead of B ``write`` calls per layer)."""
        self.k_pools[layer][:, pids, slots] = k.swapaxes(0, 1)
        self.v_pools[layer][:, pids, slots] = v.swapaxes(0, 1)
        self._dirty[layer] = True

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

    def device_pools(self, layer: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The layer's page pools as device arrays (re-uploaded only after a
        host-side write dirtied the layer)."""
        if self._dirty[layer] or self._dev[layer] is None:
            self._dev[layer] = (jnp.asarray(self.k_pools[layer]),
                                jnp.asarray(self.v_pools[layer]))
            self._dirty[layer] = False
        return self._dev[layer]

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
    context. The (page_table, seq_lens) device arrays are uploaded once and
    shared by all layers of the step.
    """

    def __init__(self, kv: PagedKVCache, seq_ids: Sequence):
        self.kv = kv
        self.seq_ids = list(seq_ids)
        pt, sl = kv.page_table(self.seq_ids)
        self._host_lens = sl
        # every layer writes the SAME (page, slot) per sequence this step —
        # resolve the addressing once, scatter per layer
        self._w_pids, self._w_slots = kv.last_slots(self.seq_ids)
        self.page_table = jnp.asarray(pt)
        self.seq_lens = jnp.asarray(sl)

    def attend(self, layer: int, q, k_new, v_new, *, scale=None,
               window: Optional[int] = None,
               softcap: Optional[float] = None):
        """Append this layer's new K/V ([B, KV, hd]) to each sequence's
        pages, then attend q ([B, H, hd]) through the page table."""
        self.kv.write_rows(layer, self._w_pids, self._w_slots,
                           np.asarray(k_new), np.asarray(v_new))
        kp, vp = self.kv.device_pools(layer)
        return ops.paged_attention(q, kp, vp, self.page_table, self.seq_lens,
                                   scale=scale, window=window, softcap=softcap)

    def bind(self, layer: int) -> _LayerBoundView:
        return _LayerBoundView(self, layer)

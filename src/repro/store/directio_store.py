"""DirectIOStore: O_DIRECT swap-in — page-cache-bypassing reads.

The mmap backend rides the kernel page cache: great when blocks re-fault
warm, but on a memory-constrained edge box the page cache is exactly the
memory the budget is trying to protect — every cached block page competes
with the resident block window, and under pressure the kernel reclaims the
cache mid-pipeline, turning "warm" swap-ins cold at the worst moment.
O_DIRECT moves unit bytes storage -> user buffer with no page-cache copy at
all: the read cost is paid once, explicitly, on the loader thread, and the
budget the MemoryLedger enforces is the whole story (no invisible
double-caching of swapped bytes).

Mechanics (this is the only backend with alignment constraints):

  * O_DIRECT requires the buffer address, the file offset, and the byte
    count to all be multiples of the logical block size (``ALIGNMENT`` =
    4096 covers every common case). Unit files are therefore padded to the
    alignment at build time, and reads land in an :class:`AlignedArena` —
    a small pool of page-aligned buffers obtained by over-allocating a
    numpy array and offsetting to the first aligned byte. Buffers are
    reused round-robin across reads (the arena is sized so a buffer is not
    rewritten before its device put completes), so steady-state swap-in
    does zero host allocations.
  * ``queue_depth > 1`` splits a unit read into that many contiguous
    aligned extents issued concurrently (``os.preadv`` per worker) —
    NVMe-class storage needs multiple outstanding requests to reach its
    bandwidth; queue_depth=1 degenerates to one sequential pread.
  * Filesystems that reject O_DIRECT (tmpfs, some overlayfs) are detected
    at ``open()`` by probing a real unit file; the store then falls back to
    buffered preads into the same arena (``direct_io`` records which path
    is live) so the backend stays portable — the accounting and the
    pipeline stages are identical either way.

Accounting: ``io_bytes`` is the ALIGNED byte count actually issued to
storage (file size after padding) — deterministic, so the CI regression
gate can byte-match it; ``nbytes`` / ``ledger_bytes`` stay logical like the
other raw-format backends.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import jax
import numpy as np

from repro.store.base import BlockStore, UnitRead
from repro.tracing import span

ALIGNMENT = 4096        # logical block size bound: address, offset, count


def _align_up(n: int, a: int = ALIGNMENT) -> int:
    return (n + a - 1) // a * a


class AlignedArena:
    """Pool of page-aligned reusable read buffers.

    numpy cannot request aligned memory directly, so each buffer
    over-allocates by one alignment unit and exposes the slice starting at
    the first aligned address. ``take(nbytes)`` returns an aligned uint8
    view of at least ``nbytes``, growing the backing buffer when a unit is
    larger than anything seen before; buffers rotate round-robin so the
    previous ``depth - 1`` reads stay intact while their device puts drain.
    """

    def __init__(self, depth: int = 4):
        assert depth >= 1, depth
        self._bufs: List[Optional[np.ndarray]] = [None] * depth
        self._next = 0
        self.allocations = 0    # observability: steady state must not grow

    def _alloc(self, nbytes: int) -> np.ndarray:
        raw = np.zeros(nbytes + ALIGNMENT, dtype=np.uint8)
        off = (-raw.ctypes.data) % ALIGNMENT
        self.allocations += 1
        return raw[off:off + nbytes]

    def take(self, nbytes: int) -> np.ndarray:
        """An aligned buffer of >= nbytes (rounded up to the alignment)."""
        need = _align_up(max(nbytes, 1))
        i = self._next
        self._next = (self._next + 1) % len(self._bufs)
        buf = self._bufs[i]
        if buf is None or buf.nbytes < need:
            buf = self._alloc(max(need, ALIGNMENT))
            self._bufs[i] = buf
        return buf[:need]


class DirectIOStore(BlockStore):
    backend = "directio"
    raw_format = True

    def __init__(self, workdir: str, queue_depth: int = 4,
                 arena_depth: int = 4, verify: bool = False):
        assert queue_depth >= 1, queue_depth
        super().__init__(workdir, verify=verify)
        self.queue_depth = queue_depth
        self.arena = AlignedArena(arena_depth)
        self.direct_io: Optional[bool] = None   # resolved by open()
        self._pool: Optional[ThreadPoolExecutor] = None

    # ------------------------------------------------------------ build
    def _write_unit(self, name: str, params: dict) -> None:
        self._write_raw(name, params)
        # pad the file to the alignment so O_DIRECT can read it whole
        path = self._path(name)
        size = os.path.getsize(path)
        pad = _align_up(size) - size
        if pad:
            with open(path, "ab") as fh:
                fh.write(b"\0" * pad)

    def open(self) -> "DirectIOStore":
        if self.direct_io is None:
            self.direct_io = self._probe_direct()
        if self._pool is None and self.queue_depth > 1:
            self._pool = ThreadPoolExecutor(
                max_workers=self.queue_depth,
                thread_name_prefix="directio")
        return self

    def _probe_direct(self) -> bool:
        """O_DIRECT support is a property of the filesystem, not the OS:
        probe with a real read so tmpfs/overlay fall back cleanly."""
        probe = next((n for n in self.order if self.skeletons[n].nbytes), None)
        if probe is None or not hasattr(os, "O_DIRECT"):
            return False
        try:
            fd = os.open(self._path(probe), os.O_RDONLY | os.O_DIRECT)
        except OSError:
            return False
        try:
            os.preadv(fd, [self.arena.take(ALIGNMENT)[:ALIGNMENT]], 0)
            return True
        except OSError:
            return False
        finally:
            os.close(fd)

    # ------------------------------------------------------------ read
    def _read_into(self, path: str, buf: np.ndarray) -> None:
        """Fill ``buf`` (aligned, whole-file size) from ``path`` with
        ``queue_depth`` concurrent aligned extents."""
        flags = os.O_RDONLY | (os.O_DIRECT if self.direct_io else 0)
        fd = os.open(path, flags)
        try:
            total = buf.nbytes
            if self._pool is None or total <= ALIGNMENT * self.queue_depth:
                got = os.preadv(fd, [buf], 0)
                assert got == total, (path, got, total)
                return
            # contiguous aligned extents, one outstanding read per worker
            chunk = _align_up(-(-total // self.queue_depth))
            spans = [(off, min(chunk, total - off))
                     for off in range(0, total, chunk)]

            def issue(span):
                off, ln = span
                got = os.preadv(fd, [buf[off:off + ln]], off)
                assert got == ln, (path, off, got, ln)

            list(self._pool.map(issue, spans))
        finally:
            os.close(fd)

    def read_unit(self, name: str) -> UnitRead:
        from repro.core.skeleton import assemble_np
        skel = self.skeletons[name]
        n = skel.nbytes
        if n == 0:
            return self._empty_unit(name)
        aligned = _align_up(n)
        with span("swap.read", unit=name):
            t0 = time.perf_counter()
            buf = self.arena.take(aligned)
            self._read_into(self._path(name), buf)
            # digest covers the padded file (what storage actually delivered)
            self._verify_payload(name, buf)
            t1 = time.perf_counter()
        with span("swap.unpack", unit=name):
            host_tree = assemble_np(skel, buf[:n])     # views: zero copy
            t2 = time.perf_counter()
        with span("swap.dispatch", unit=name):
            # the device put MUST copy out of the arena before the buffer
            # rotates back around — block here (loader thread) to guarantee
            dev = jax.device_put(host_tree)            # batched puts
            jax.block_until_ready(dev)
            t3 = time.perf_counter()
        stages = (("read", t0, t1), ("unpack", t1, t2), ("dispatch", t2, t3))
        return UnitRead(dev, aligned, n, t1 - t0, t3 - t1, stages=stages)

    def stored_nbytes(self, name: str) -> int:
        return _align_up(self.skeletons[name].nbytes)

    def resident_nbytes(self, name: str) -> int:
        """What stays resident is the device copy (logical bytes); the
        alignment padding only exists on storage and in the fixed-size
        arena, never per resident unit."""
        return self.skeletons[name].nbytes

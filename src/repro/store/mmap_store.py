"""MmapStore: the paper's zero-copy swap-in path, extracted from SwapEngine.

Memory-maps the unit file (direct-I/O analogue: no page-cache staging copy),
assembles host-side by reference (numpy views over the map — O(depth) pointer
writes), then pays the ONE irreducible host->device transfer per unit.
Swap-out stays write-back-free: parameters are immutable, drop references.

``assembly="dummy"`` is the w/o-mod-ske ablation arm: same zero-copy I/O, but
framework-default assembly — instantiate a dummy unit and copy parameters in
(per-tensor copies, 2x resident during assembly).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.store.base import BlockStore, UnitRead
from repro.tracing import span


class MmapStore(BlockStore):
    backend = "mmap"
    raw_format = True

    def __init__(self, workdir: str, assembly: str = "ref",
                 verify: bool = False):
        assert assembly in ("ref", "dummy"), assembly
        super().__init__(workdir, verify=verify)
        self.assembly = assembly

    def _write_unit(self, name: str, params: dict) -> None:
        self._write_raw(name, params)

    def resident_nbytes(self, name: str) -> int:
        n = self.skeletons[name].nbytes
        return 2 * n if self.assembly == "dummy" else n

    def read_unit(self, name: str) -> UnitRead:
        from repro.core.skeleton import assemble_dummy, assemble_np
        skel = self.skeletons[name]
        n = skel.nbytes
        if n == 0:
            return self._empty_unit(name)
        with span("swap.read", unit=name):
            t0 = time.perf_counter()
            buf = np.memmap(self._path(name), dtype=np.uint8, mode="r")
            # verify (opt-in) trades mmap's lazy page-in for integrity: the
            # CRC pass faults every page on the loader thread, so a corrupt
            # unit is rejected here instead of being device-put and silently
            # computed on
            self._verify_payload(name, buf)
            t1 = time.perf_counter()
        dummy = self.assembly == "dummy"
        with span("swap.unpack", unit=name):
            host_tree = (assemble_dummy(skel, buf) if dummy  # dummy-model copies
                         else assemble_np(skel, buf))        # views: zero copy
            t2 = time.perf_counter()
        with span("swap.dispatch", unit=name):
            dev = jax.device_put(host_tree)       # the one (batched) DMA
            t3 = time.perf_counter()
        extra = 2 * n if dummy else n
        # mmap blurs the read stage: the memmap open is O(1) and the actual
        # page-ins fault lazily inside the device put, so "dispatch" carries
        # the storage traffic too (documented in docs/BENCHMARKS.md).
        stages = (("read", t0, t1), ("unpack", t1, t2), ("dispatch", t2, t3))
        return UnitRead(dev, n, extra, t1 - t0, t3 - t1, stages=stages)


class LayerStore(MmapStore):
    """Backwards-compatible name for the default raw store (per-layer flat
    files + resident skeletons). Prefer :class:`MmapStore` in new code."""

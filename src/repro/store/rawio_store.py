"""RawIOStore: read()-based swap-in — the w/o-uni-add (``copy_in``) arm.

The standard framework load path the paper ablates against: read() lands the
unit in a page-cache copy, a staging copy materializes it in the process
heap, then the device transfer — 2x resident bytes per unit (3x for models
dispatched through a GPU runtime, which adds its own dispatch copy). Kept as
a first-class backend for ablation parity and because on some storage tiers
(e.g. network filesystems where mmap page faults serialize) buffered read()
is genuinely the faster channel.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.store.base import BlockStore, UnitRead
from repro.tracing import span


class RawIOStore(BlockStore):
    backend = "rawio"
    raw_format = True

    def __init__(self, workdir: str, gpu_dispatch: bool = False,
                 verify: bool = False):
        super().__init__(workdir, verify=verify)
        self.gpu_dispatch = gpu_dispatch

    def _write_unit(self, name: str, params: dict) -> None:
        self._write_raw(name, params)

    def resident_nbytes(self, name: str) -> int:
        return (3 if self.gpu_dispatch else 2) * self.skeletons[name].nbytes

    def read_unit(self, name: str) -> UnitRead:
        from repro.core.skeleton import assemble_np
        skel = self.skeletons[name]
        n = skel.nbytes
        if n == 0:
            return self._empty_unit(name)
        with span("swap.read", unit=name):
            t0 = time.perf_counter()
            with open(self._path(name), "rb") as fh:   # read(): page-cache copy
                raw = fh.read()
            staged = np.frombuffer(raw, np.uint8).copy()   # staging copy
            self._verify_payload(name, staged)
            t1 = time.perf_counter()
        with span("swap.unpack", unit=name):
            host_tree = assemble_np(skel, staged)
            t2 = time.perf_counter()
        with span("swap.dispatch", unit=name):
            dev = jax.device_put(host_tree)            # device transfer
            if self.gpu_dispatch:
                dev = jax.tree.map(jnp.array, dev)     # dispatch copy (.to('cuda'))
            t3 = time.perf_counter()
        extra = (3 if self.gpu_dispatch else 2) * n
        stages = (("read", t0, t1), ("unpack", t1, t2), ("dispatch", t2, t3))
        return UnitRead(dev, n, extra, t1 - t0, t3 - t1, stages=stages)

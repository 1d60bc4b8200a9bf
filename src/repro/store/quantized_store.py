"""QuantizedStore: int8/int4 per-channel quantized swap units.

The paper's LLM outlook (§ "insights for deploying LLMs") points at raw I/O
bytes per block as the bottleneck once the redundant copies are gone. This
backend attacks exactly that: at BUILD time every large float tensor of a
unit is quantized to symmetric per-channel int8 (values + one fp32 scale per
output channel, ~4x fewer bytes than fp32) or packed int4 (two values per
carrier byte, ~8x — ``bits=4``), cutting the bytes a swap-in must move from
storage to host accordingly. At SWAP-IN the quantized payload is memmapped
(zero host copies, like the snet path) and transferred host->device still
quantized. What happens next is the ``eager`` knob:

  * ``eager=True``  (default, the PR 2 behaviour): the fp tree is
    reconstructed on device by the Pallas ``dequant_int8`` kernel (int4
    unpacks first) — the dequant rides the H2D transfer the swap-in pays
    anyway;
  * ``eager=False`` (the FUSED path, ROADMAP (f)): leaves whose consumers
    route through ``models/layers.linear`` — 2-D matmul weights under a
    fused-routable key (:data:`FUSED_STREAM_KEYS`) — come back as
    :class:`~repro.kernels.qtensor.QuantizedTensor`: fp is NEVER
    materialized for them; they stream straight through the fused
    dequant-matmul (kernels/swap_linear_q.py), so HBM->VMEM DMA and the
    VMEM weight window also shrink 2-4x. Leaves the fused kernel CANNOT
    stream (conv stacks, 3-D expert einsums, embeddings) are dequantized
    HERE, on the loader thread — dequant-at-use on the executor would
    serialize the dequant into the compute phase of every pass, which is
    exactly the fused-path overlap gap this store used to have. The I/O
    win (quantized bytes on the storage channel) applies to every leaf
    either way.

Pipeline contract (the PR 6 fix, asserted by tests/test_overlap_timeline):
the ENTIRE quantized payload is forced host-resident by one sequential
read at the top of ``read_unit`` — the old code memmapped the file and let
the carrier bytes fault in lazily inside the device put, so the host read
of block i+1 rode on the dispatch stage instead of overlapping block i's
compute. Every stage (read -> unpack -> dispatch, including the device-put
flush) runs and COMPLETES on the loader thread; the executor only ever
waits on a finished unit. In lazy mode the non-streamable dequant is
NUMPY on the loader ("unpack") — one device put per leaf, no per-leaf
device-op storm on the swap-in critical path.

Accounting (tested contract):
  * ``io_bytes`` / ``SwapStats.bytes_swapped`` — the QUANTIZED payload size
    (what actually crossed the storage channel);
  * ``ledger_bytes`` — with ``eager=True`` the stored (quantized) size, the
    PR 2 modeling convention (the repro materializes the fp tree as the
    execution artifact and reports that side as ``SwapStats.bytes_logical``
    so nothing is hidden); with ``eager=False`` the HONEST mixed residency:
    quantized payload + scales for QuantizedTensor leaves, logical fp bytes
    for loader-dequantized leaves — so the planner packs against what the
    ledger will really hold;
  * ``quantized_bytes`` — bytes delivered still-quantized (lazy mode only);
  * ``nbytes`` stays LOGICAL (dequantized) — partitioning and block-size
    reasoning are unchanged (the planner separately consults
    ``resident_nbytes`` to see the smaller working set).

What gets quantized: float leaves with ndim >= 2 and >= ``min_quant_size``
elements (weight matrices, conv stacks). 1-D leaves (norm gains, biases) and
small tensors are stored raw — they are bytes-cheap and accuracy-critical,
so the round-trip error bound (``|x̂ - x| <= max|x[:, c]| / 254`` at int8,
``/ 14`` at int4; see kernels/dequant.py) applies only where it is well
conditioned. Per-MODEL eligibility and precision are config knobs
(``ModelConfig.quant_eligible`` / ``swap_precision``): architectures whose
recurrent dynamics amplify weight error opt out and fall back to the mmap
backend.

Mixed precision (``plan=...``): instead of one store-wide bit-width, a
calibration-derived plan (repro/calibrate/) assigns fp | int8 | int4 PER
UNIT. Each ``QLeaf`` records its own ``bits`` and the read path dispatches
on the leaf, so one store mixes exact and quantized units freely; the
per-precision stored-byte split flows out through
``UnitRead.precision_bytes`` into ``SwapStats.bytes_by_precision``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.qtensor import FUSED_WEIGHT_KEYS
from repro.store.base import BlockStore, UnitRead
from repro.tracing import span

MIN_QUANT_SIZE = 1024       # elements; smaller leaves are stored raw

# keys whose 2-D weights stream through the fused dequant-matmul and may
# therefore stay quantized-resident; "w" is the generic fc weight key of the
# vision models, whose consumer is also models/layers.linear
FUSED_STREAM_KEYS = FUSED_WEIGHT_KEYS | {"w"}

# per-unit bit-width labels for the byte accounting; 0 = raw/fp
BITS_PRECISION = {0: "fp", 8: "int8", 4: "int4"}


def quantizable(arr: np.ndarray, min_quant_size: int = MIN_QUANT_SIZE) -> bool:
    """The store's quantization predicate (module docstring, "What gets
    quantized") — shared with the calibration profiler so measured
    sensitivity covers exactly the leaves the store will quantize."""
    return (arr.ndim >= 2 and arr.size >= min_quant_size
            and jnp.issubdtype(jnp.dtype(arr.dtype), jnp.floating))


def unit_stored_nbytes(params, bits: int,
                       min_quant_size: int = MIN_QUANT_SIZE) -> int:
    """Exact stored payload size of one unit at a bit-width WITHOUT building
    the store: every ``put`` segment below pads to ALIGN, so the analytic
    sum of aligned segment sizes equals the file size byte-for-byte. The
    precision policy packs against this table. ``bits=0`` = all-raw (fp)."""
    from repro.core.skeleton import _align
    assert bits in (0, 4, 8), bits
    total = 0
    for leaf in jax.tree.leaves(params):
        arr = np.asarray(leaf)
        if bits and quantizable(arr, min_quant_size):
            rows = int(np.prod(arr.shape[:-1]))
            cols = int(arr.shape[-1])
            qrows = rows if bits == 8 else (rows + 1) // 2
            total += _align(qrows * cols) + _align(4 * cols)
        else:
            total += _align(arr.nbytes)
    return total


@dataclass(frozen=True)
class QLeaf:
    """One leaf inside a unit's quantized payload file.

    ``scale_offset < 0`` marks a raw (unquantized) leaf; otherwise the leaf
    is quantized [rows, cols] (``rows`` = LOGICAL rows of the channel grid;
    the int4 carrier holds ceil(rows/2) payload rows) at ``offset`` with
    fp32 [cols] scales at ``scale_offset``. ``dtype`` is the ORIGINAL dtype
    dequant restores. ``fusable`` marks leaves the fused kernel can stream
    still-quantized (2-D, key in :data:`FUSED_STREAM_KEYS`); in lazy mode
    every other quantized leaf is dequantized on the loader thread.
    ``bits`` is the PER-LEAF bit-width (8 | 4 for quantized leaves, 0 for
    raw) — under a mixed-precision plan different units of one store carry
    different widths, so the read path dispatches on the leaf, never on a
    store-global setting."""
    offset: int
    nbytes: int
    shape: Tuple[int, ...]
    dtype: str
    scale_offset: int = -1
    rows: int = 0
    cols: int = 0
    fusable: bool = False
    bits: int = 0


@dataclass
class QuantMeta:
    leaves: List[QLeaf]
    stored_nbytes: int
    resident_lazy: int = 0   # mixed residency of the eager=False read (bytes)
    precision_bytes: Dict[str, int] = None  # stored bytes per fp|int8|int4


class QuantizedStore(BlockStore):
    backend = "quant"
    raw_format = False

    def __init__(self, workdir: str, min_quant_size: int = MIN_QUANT_SIZE,
                 bits: int = 8, eager: bool = True, verify: bool = False,
                 plan=None):
        """``plan`` switches the store to PER-UNIT mixed precision: a dict
        ``{unit_name: 0|8|4}`` (0 = raw fp) or any object with a
        ``bits_map()`` method returning one — duck-typed so this module
        never imports the calibrate package that produces
        ``PrecisionPlan``s. Units the plan does not name are stored RAW:
        an unprofiled unit must round-trip bit-exactly, not inherit a
        bit-width nobody measured. Without a plan the store is uniform at
        ``bits`` (the pre-existing behaviour)."""
        assert bits in (8, 4), bits
        super().__init__(workdir, verify=verify)
        self.min_quant_size = min_quant_size
        self.bits = bits
        self.eager = eager
        bm = plan.bits_map() if hasattr(plan, "bits_map") else plan
        self.plan = dict(bm) if bm is not None else None
        if self.plan is not None:
            bad = {b for b in self.plan.values() if b not in (0, 4, 8)}
            assert not bad, f"plan bit-widths must be 0|4|8, got {bad}"
            self.suffix = ".qm"
        else:
            self.suffix = ".q8" if bits == 8 else ".q4"
        self._qmeta: Dict[str, QuantMeta] = {}

    @property
    def precision(self) -> str:
        if self.plan is not None:
            return "mixed"
        return "int8" if self.bits == 8 else "int4"

    def _unit_bits(self, name: str) -> int:
        return self.bits if self.plan is None else self.plan.get(name, 0)

    # ------------------------------------------------------------ build
    def _write_unit(self, name: str, params: dict) -> None:
        from repro.core.skeleton import ALIGN, skeleton_of
        from repro.kernels.dequant import quantize_int4, quantize_int8
        bits_u = self._unit_bits(name)
        quantize = quantize_int8 if bits_u == 8 else quantize_int4
        flat, _ = jax.tree.flatten_with_path(params)
        # logical skeleton (nbytes/meta) WITHOUT materializing the flat fp
        # buffer — the payload below is this store's only serialization
        self.skeletons[name] = skeleton_of(params)
        blob = bytearray()

        def put(b: bytes) -> int:
            off = len(blob)
            blob.extend(b)
            blob.extend(b"\0" * ((-len(blob)) % ALIGN))
            return off

        qleaves: List[QLeaf] = []
        resident_lazy = 0
        pbytes = {p: 0 for p in BITS_PRECISION.values()}
        for path, leaf in flat:
            arr = np.ascontiguousarray(np.asarray(leaf))
            seg0 = len(blob)
            if bits_u and quantizable(arr, self.min_quant_size):
                key = getattr(path[-1], "key", None) if path else None
                fusable = arr.ndim == 2 and key in FUSED_STREAM_KEYS
                q, scales = quantize(arr)
                off = put(q.tobytes())
                soff = put(scales.tobytes())
                rows = int(np.prod(arr.shape[:-1]))
                qleaves.append(QLeaf(off, q.nbytes, tuple(arr.shape),
                                     str(arr.dtype), soff, rows, q.shape[1],
                                     fusable, bits_u))
                resident_lazy += (q.nbytes + scales.nbytes if fusable
                                  else arr.nbytes)
            else:
                off = put(arr.tobytes())
                qleaves.append(QLeaf(off, arr.nbytes, tuple(arr.shape),
                                     str(arr.dtype)))
                resident_lazy += arr.nbytes
            # aligned segment growth, bucketed by the leaf's stored width
            pbytes[BITS_PRECISION[qleaves[-1].bits]] += len(blob) - seg0
        with open(self._path(name), "wb") as fh:
            fh.write(bytes(blob))
        self._qmeta[name] = QuantMeta(qleaves, len(blob), resident_lazy,
                                      pbytes)

    # ------------------------------------------------------------ read
    def read_unit(self, name: str) -> UnitRead:
        from repro.kernels.dequant import unpack_int4
        from repro.kernels.ops import dequant_int8
        from repro.kernels.qtensor import QuantizedTensor
        from repro.kernels.ref import unpack_int4_ref
        skel = self.skeletons[name]
        if skel.nbytes == 0:
            return self._empty_unit(name)
        meta = self._qmeta[name]
        lazy = not self.eager
        with span("swap.read", unit=name):
            t0 = time.perf_counter()
            # read: ONE sequential buffered read forces the whole carrier
            # payload host-resident on the loader thread — a memmap here
            # would defer the storage traffic to page faults inside the
            # device puts below, where it can no longer overlap the executor
            # (module docstring, "Pipeline contract").
            buf = np.fromfile(self._path(name), dtype=np.uint8)
            # integrity over the CARRIER bytes: a flipped nibble in a
            # packed-int4 payload is caught here, never dequantized into
            # wrong weights
            self._verify_payload(name, buf)
            t1 = time.perf_counter()
        with span("swap.unpack", unit=name):
            # unpack: host-side work over the payload. Raw and streamable
            # leaves are pure views; in lazy mode the quantized leaves the
            # fused kernel CANNOT stream dequantize here in numpy — host
            # FLOPs on the otherwise-idle loader core, one device put per
            # leaf, instead of a per-leaf device-op storm or dequant-at-use
            # inside executor compute.
            host: list = []
            for ql in meta.leaves:
                dt = jnp.dtype(ql.dtype)
                if ql.scale_offset < 0:            # raw leaf
                    host.append((ql, buf[ql.offset:ql.offset + ql.nbytes]
                                 .view(dt.type).reshape(ql.shape), None))
                    continue
                qv = buf[ql.offset:ql.offset + ql.nbytes] \
                    .view(np.int8).reshape(-1, ql.cols)
                sv = buf[ql.scale_offset:ql.scale_offset + 4 * ql.cols] \
                    .view(np.float32)
                if lazy and not ql.fusable:
                    vals = unpack_int4(qv, ql.rows) if ql.bits == 4 else qv
                    # one fused multiply pass (int8 x scales -> fp32 out);
                    # the naive astype()*astype() chain costs 3 full copies
                    fp = np.multiply(vals, sv[None, :], dtype=np.float32)
                    if dt.type is not np.float32:
                        fp = fp.astype(dt.type)
                    host.append((ql, fp.reshape(ql.shape), None))
                else:
                    host.append((ql, qv, sv))
            t2 = time.perf_counter()
        with span("swap.dispatch", unit=name):
            # dispatch: host -> device puts (eager mode keeps the seed's
            # on-device Pallas dequant — it rides the H2D transfer), flushed
            # HERE so the executor never inherits loader work. All leaves go
            # up in ONE batched jax.device_put — per-call dispatch overhead
            # (~100-200us) over dozens of leaves is the single largest loader
            # cost after the dequant itself
            arrs: list = []
            for _, qv, sv in host:
                arrs.append(qv)
                if sv is not None:
                    arrs.append(sv)
            dev = iter(jax.device_put(arrs))
            leaves = []
            qbytes = 0
            for ql, qv, sv in host:
                q = next(dev)
                if sv is None:
                    leaves.append(q)
                    continue
                s = next(dev)
                if lazy:                           # fused path: stay quantized
                    leaves.append(QuantizedTensor(q, s, ql.shape, ql.dtype,
                                                  ql.bits))
                    qbytes += ql.nbytes + 4 * ql.cols
                    continue
                vals = unpack_int4_ref(q, ql.rows) if ql.bits == 4 else q
                leaves.append(dequant_int8(vals, s, jnp.dtype(ql.dtype).type)
                              .reshape(ql.shape))
            tree = jax.tree.unflatten(skel.treedef, leaves)
            jax.block_until_ready(tree)
            t3 = time.perf_counter()
        stored = meta.stored_nbytes
        ledger = meta.resident_lazy if lazy else stored
        stages = (("read", t0, t1), ("unpack", t1, t2), ("dispatch", t2, t3))
        return UnitRead(tree, stored, ledger, t1 - t0, t3 - t1,
                        quantized_bytes=qbytes, stages=stages,
                        precision_bytes={k: v for k, v in
                                         (meta.precision_bytes or {}).items()
                                         if v})

    # ------------------------------------------------------------ sizes
    def stored_nbytes(self, name: str) -> int:
        return self._qmeta[name].stored_nbytes if name in self._qmeta \
            else self.skeletons[name].nbytes

    def resident_nbytes(self, name: str) -> int:
        """Eager mode holds the stored (quantized) payload convention; lazy
        mode holds the honest mixed residency (QuantizedTensor payloads for
        fusable leaves, restored fp for everything else)."""
        if not self.eager and name in self._qmeta:
            return self._qmeta[name].resident_lazy
        return self.stored_nbytes(name)

    def meta_bytes(self) -> int:
        """Skeletons plus the per-leaf quant refs (still KB-scale/model)."""
        base = super().meta_bytes()
        return base + sum(64 + 72 * len(m.leaves)
                          for m in self._qmeta.values())

"""Tiered block-store subsystem: backend bit-identity / bounded error,
collision-free file naming, quantized I/O + ledger accounting, the Pallas
dequant kernel vs its numpy reference, and size-aware cache admission.

Documented quantization tolerance (see kernels/dequant.py): symmetric
round-to-nearest per-channel int8 reproduces a tensor x within
``|x_hat - x| <= scale_c / 2 = max|x[:, c]| / 254`` elementwise.
"""
import dataclasses
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import ShapeConfig
from repro.core.cost_model import DelayModel
from repro.core.runtime import SwappedModel
from repro.core.swap_engine import (BlockCache, MemoryLedger, SwapEngine,
                                    size_aware_policy)
from repro.kernels.dequant import dequant_int8, quantize_int8
from repro.models.transformer import Model
from repro.store import MmapStore, RawIOStore, build_store, escape_name

from conftest import make_batch


def _units(seed=0, n=3, shape=(64, 128)):
    rng = np.random.default_rng(seed)
    return [(f"u{i:02d}", {"w": rng.standard_normal(shape).astype(np.float32),
                           "g": rng.standard_normal(shape[0]).astype(np.float32)})
            for i in range(n)]


def _setup(arch, seed=0):
    cfg = dataclasses.replace(ARCHS[arch].reduced(), dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    shape = ShapeConfig("p", 32, 2, "prefill")
    batch = make_batch(cfg, shape)
    return cfg, model, params, batch


# ------------------------------------------------------------ path escaping
def test_store_path_collision_free():
    """Regression: the old ``name.replace('/', '_')`` mapped "a/b" and "a_b"
    to the SAME file — the second build clobbered the first unit's bytes."""
    assert escape_name("a/b") != escape_name("a_b")
    rng = np.random.default_rng(0)
    w1 = rng.standard_normal((8, 16)).astype(np.float32)
    w2 = rng.standard_normal((8, 16)).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        store = build_store([("a/b", {"w": w1}), ("a_b", {"w": w2})], d)
        r1 = store.read_unit("a/b")
        r2 = store.read_unit("a_b")
    np.testing.assert_array_equal(np.asarray(r1.params["w"]), w1)
    np.testing.assert_array_equal(np.asarray(r2.params["w"]), w2)


def test_escape_name_injective_on_tricky_names():
    names = ["a/b", "a_b", "a__b", "a_/b", "a/_b", "a_.b", "a//b", "a"]
    escaped = [escape_name(n) for n in names]
    assert len(set(escaped)) == len(names)


# ------------------------------------------------------------ bit identity
@pytest.mark.parametrize("backend", ["mmap", "rawio"])
def test_raw_backends_bit_identical(backend):
    units = _units()
    with tempfile.TemporaryDirectory() as d:
        store = build_store(units, d, backend=backend)
        for name, params in units:
            r = store.read_unit(name)
            for k in params:
                np.testing.assert_array_equal(np.asarray(r.params[k]),
                                              params[k])
            assert r.io_bytes == store.nbytes(name)
            assert r.ledger_bytes >= store.nbytes(name)


def test_quantized_roundtrip_bounded_error():
    """Per-channel int8 round-trip stays within the documented bound
    |x_hat - x| <= scale_c / 2; small 1-D leaves (norm gains) stay exact."""
    units = _units()
    with tempfile.TemporaryDirectory() as d:
        store = build_store(units, d, backend="quant")
        for name, params in units:
            r = store.read_unit(name)
            w, w_hat = params["w"], np.asarray(r.params["w"])
            scales = np.max(np.abs(w), axis=0) / 127.0
            assert np.all(np.abs(w_hat - w) <= scales[None, :] / 2 + 1e-7)
            # raw (unquantized) leaf: exact
            np.testing.assert_array_equal(np.asarray(r.params["g"]),
                                          params["g"])


def test_quantized_store_moves_fewer_bytes():
    units = _units(shape=(128, 256))
    with tempfile.TemporaryDirectory() as d:
        store = build_store(units, d, backend="quant")
        for name, _ in units:
            assert store.stored_nbytes(name) * 3 < store.nbytes(name)
            r = store.read_unit(name)
            assert r.io_bytes == store.stored_nbytes(name)


# ------------------------------------------------------------ dequant kernel
@pytest.mark.parametrize("R,C", [(8, 128), (200, 96), (1, 7), (300, 2100)])
@pytest.mark.parametrize("out_dtype", ["float32", "bfloat16"])
def test_dequant_kernel_matches_numpy_ref(R, C, out_dtype):
    """The Pallas kernel (interpret mode) vs a plain numpy dequant."""
    rng = np.random.default_rng(42)
    q = rng.integers(-127, 128, (R, C)).astype(np.int8)
    scales = (rng.random(C).astype(np.float32) + 0.1) / 127.0
    got = np.asarray(dequant_int8(jax.numpy.asarray(q),
                                  jax.numpy.asarray(scales),
                                  jax.numpy.dtype(out_dtype).type,
                                  interpret=True), np.float32)
    want = q.astype(np.float32) * scales[None, :]
    if out_dtype == "bfloat16":
        want = want.astype(jax.numpy.bfloat16).astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_quantize_int8_reference_properties():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((64, 32)).astype(np.float32) * 3.0
    q, scales = quantize_int8(x)
    assert q.dtype == np.int8 and scales.shape == (32,)
    assert np.abs(q).max() <= 127
    x_hat = q.astype(np.float32) * scales[None, :]
    assert np.all(np.abs(x_hat - x) <= scales[None, :] / 2 + 1e-7)
    # zero channel: scale 1.0, exact zero round-trip
    x[:, 3] = 0.0
    q, scales = quantize_int8(x)
    assert scales[3] == 1.0 and np.all(q[:, 3] == 0)


# ------------------------------------------------------- engine accounting
def test_quant_ledger_charges_quantized_resident_bytes():
    """The resident swap unit of the quant backend is the quantized payload:
    the ledger (and therefore the shared budget) is charged stored bytes,
    not the dequantized logical bytes."""
    units = _units(shape=(128, 256))
    with tempfile.TemporaryDirectory() as d:
        store = build_store(units, d, backend="quant")
        eng = SwapEngine(store)
        h = eng.swap_in([n for n, _ in units])
        expect = sum(store.stored_nbytes(n) for n, _ in units)
        assert h.resident_bytes == expect
        assert eng.ledger.resident == expect
        assert h.nbytes == sum(store.nbytes(n) for n, _ in units)
        eng.swap_out(h)
        assert eng.ledger.resident == 0
        eng.close()


def test_quant_swapin_moves_3x_fewer_bytes_than_mmap():
    """Acceptance: QuantizedStore swap-in moves >= 3x fewer bytes from store
    to host than MmapStore on the same model, per SwapStats."""
    cfg, model, params, batch = _setup("qwen2.5-3b")
    swapped = {}
    for backend in ("mmap", "quant"):
        with tempfile.TemporaryDirectory() as d:
            sm = SwappedModel(model, params, d, store_backend=backend)
            assert sm.store_backend == backend
            sm.partition(budget=8 * 1024 * 1024, dm=DelayModel(),
                         batch=2, seq=32)
            _, stats = sm.forward(batch)
            swapped[backend] = stats["bytes_swapped"]
            assert stats["bytes_logical"] > 0
            assert stats["store_backend"] == backend
            sm.close()
    assert swapped["quant"] * 3 <= swapped["mmap"]


def test_quant_swapped_forward_close_to_reference():
    """End-to-end: swapped inference through int8 units stays close to the
    unswapped fp32 model (bounded per-channel error, cosine fidelity)."""
    cfg, model, params, batch = _setup("qwen2.5-3b")
    ref, _ = jax.jit(model.prefill)(params, batch)
    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d, store_backend="quant")
        sm.partition(budget=8 * 1024 * 1024, dm=DelayModel(), batch=2, seq=32)
        logits, _ = sm.forward(batch)
        sm.close()
    a = np.asarray(logits, np.float64).ravel()
    b = np.asarray(ref, np.float64).ravel()[-a.size:]
    cos = a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30)
    assert cos > 0.98


def test_quant_ineligible_config_falls_back_to_mmap():
    """Per-model eligibility (configs): a quant_eligible=False arch served
    with store_backend='quant' silently uses the exact mmap store."""
    cfg, model, params, batch = _setup("rwkv6-3b")
    assert not cfg.quant_eligible
    ref, _ = jax.jit(model.prefill)(params, batch)
    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d, store_backend="quant")
        assert sm.store_backend == "mmap"
        assert isinstance(sm.store, MmapStore)
        sm.partition(budget=8 * 1024 * 1024, dm=DelayModel(), batch=2, seq=32)
        logits, _ = sm.forward(batch)
        sm.close()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mode_flags_resolve_against_raw_store():
    """Ablation modes reinterpret one set of raw files; quant rejects them."""
    units = _units()
    with tempfile.TemporaryDirectory() as d:
        store = build_store(units, d, backend="mmap")
        eng = SwapEngine(store, mode="copy_in")
        assert isinstance(eng.store, RawIOStore)
        eng.close()
        eng = SwapEngine(store, mode="dummy_asm")
        assert isinstance(eng.store, MmapStore) and eng.store.assembly == "dummy"
        eng.close()
    with tempfile.TemporaryDirectory() as d:
        qstore = build_store(units, d, backend="quant")
        with pytest.raises(TypeError):
            SwapEngine(qstore, mode="copy_in")


def test_store_backend_rejects_mode_combination():
    units = _units()
    from repro.core.runtime import SwappedSequential
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="requires mode='snet'"):
            SwappedSequential(units, lambda i, p, x: x, d,
                              mode="copy_in", store_backend="quant")


# ------------------------------------------------------- cache admission
def test_size_aware_policy_admits_cofitting_size_classes():
    """ROADMAP item (d): admission from the partition table's per-unit
    sizes. All units of a size class enter together or not at all."""
    sizes = {"embed": 5, "head": 5, "l0": 20, "l1": 20, "l2": 20}
    # capacity 30: both small units (10) fit; adding the 60-byte layer
    # class would not -> threshold 5
    policy = size_aware_policy(sizes, capacity=30)
    assert policy("embed", 5) and policy("head", 5)
    assert not policy("l0", 20)
    # capacity 80: small class (10) + layer class (60) both fit
    policy = size_aware_policy(sizes, capacity=80)
    assert policy("l0", 20) and policy("embed", 5)
    # unknown units fall back to their observed size
    assert policy("new_small", 3)
    assert not policy("new_big", 10**9)
    # zero-size units never admitted
    assert not policy("empty", 0)


def test_cache_policy_constructor_argument():
    ledger = MemoryLedger()
    cache = BlockCache(100, ledger, policy=lambda name, n: name.startswith("hot"))
    assert cache.admits("hot1", 10**9)
    assert not cache.admits("cold", 1)
    cache.pin(["cold_pinned"])
    assert cache.admits("cold_pinned", 1)      # pinned bypasses policy
    # legacy default: admit_frac heuristic still the fallback
    legacy = BlockCache(100, ledger, admit_frac=0.25)
    assert legacy.admits("x", 25) and not legacy.admits("x", 26)
    legacy.set_policy(lambda name, n: True)
    assert legacy.admits("x", 26)


def test_multi_model_plan_installs_size_aware_policy():
    from repro.core.multi_model import MultiModelRuntime
    setups = {a: _setup(a, seed=i)
              for i, a in enumerate(["qwen2.5-3b", "gemma2-9b"])}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(24 * 1024 * 1024, cache_frac=0.25)
        for a, (cfg, model, params, _) in setups.items():
            rt.add_model(a, model, params, d)
        assert rt.cache.policy is None
        rt.plan(batch=2, seq=32)
        assert rt.cache.policy is not None
        # the small hot units (embed/head) are admitted, full layers not
        sm = rt.models["qwen2.5-3b"]
        embed = "qwen2.5-3b/embed"
        layer = next(n for n in sm.store.order if "layer" in n)
        assert rt.cache.admits(embed, sm.store.stored_nbytes(embed))
        assert not rt.cache.admits(layer, sm.store.stored_nbytes(layer))
        rt.close()


def test_multi_model_mixed_backends_share_budget():
    """One tenant on quant units, one on mmap, one shared budget: both stay
    lossless-or-bounded and the ledger never exceeds the budget."""
    from repro.core.multi_model import MultiModelRuntime
    budget = 24 * 1024 * 1024
    setups = {a: _setup(a, seed=i)
              for i, a in enumerate(["qwen2.5-3b", "gemma2-9b"])}
    with tempfile.TemporaryDirectory() as d:
        rt = MultiModelRuntime(budget, cache_frac=0.25)
        rt.add_model("qwen2.5-3b", setups["qwen2.5-3b"][1],
                     setups["qwen2.5-3b"][2], d, store_backend="quant")
        rt.add_model("gemma2-9b", setups["gemma2-9b"][1],
                     setups["gemma2-9b"][2], d)
        rt.plan(batch=2, seq=32)
        for a in setups:
            rt.forward(a, setups[a][3])
        st = rt.stats()
        rt.close()
    assert st["peak_resident_mb"] * 1e6 <= budget
    assert st["models"]["qwen2.5-3b"]["store_backend"] == "quant"
    assert st["models"]["gemma2-9b"]["store_backend"] == "mmap"
    q = st["models"]["qwen2.5-3b"]
    assert q["bytes_swapped_mb"] * 3 < q["bytes_logical_mb"]

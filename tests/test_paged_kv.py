"""Paged KV cache + paged attention kernel (serving tentpole).

The kernel property: attention gathered through an ARBITRARY page table must
match contiguous flash attention on the same context within fp tolerance —
paging is a memory layout, not a math change. The cache property: pages are
charged to the shared MemoryLedger and the ledger NEVER exceeds its budget,
no matter how concurrent admits/retires interleave.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.swap_engine import MemoryLedger
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.serving.paged_kv import (PagedBatchView, PagedKVCache,
                                    page_bytes_for)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


def _random_paged(rng_key, B, H, KV, hd, T, max_pages, dtype,
                  seq_lens):
    """Random q + [KV, P, T, hd] page pools + a SHUFFLED page table
    covering seq_lens."""
    kq, kk, kv = jax.random.split(rng_key, 3)
    q = jax.random.normal(kq, (B, H, hd), dtype) * 0.5
    k_pages = jax.random.normal(kk, (KV, max_pages + 1, T, hd), dtype) * 0.5
    v_pages = jax.random.normal(kv, (KV, max_pages + 1, T, hd), dtype) * 0.5
    k_pages = k_pages.at[:, 0].set(0)     # zero sentinel
    v_pages = v_pages.at[:, 0].set(0)
    NP = max(-(-int(s) // T) for s in seq_lens)
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, max_pages + 1))
    pt = np.zeros((B, NP), np.int32)
    used = 0
    for b, s in enumerate(seq_lens):
        n = -(-int(s) // T)
        pt[b, :n] = ids[used:used + n]
        used += n
    assert used <= max_pages
    return q, k_pages, v_pages, jnp.asarray(pt), jnp.asarray(
        np.asarray(seq_lens, np.int32))


def _gather(pool, pages):
    """A [KV, P, T, hd] pool's pages in table order -> [n*T, KV, hd]."""
    pool = np.asarray(pool)
    return pool[:, pages].reshape(pool.shape[0], -1,
                                  pool.shape[-1]).swapaxes(0, 1)


def _check_paged_kernel(dtype, window, softcap, KV):
    B, H, hd, T = 3, 8, 64, 8
    seq_lens = [5, 23, 16]
    q, kp, vp, pt, sl = _random_paged(jax.random.key(0), B, H, KV, hd, T,
                                      16, dtype, seq_lens)
    got = paged_attention(q, kp, vp, pt, sl, window=window, softcap=softcap,
                          interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, pt, sl, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap", [
    (None, None), (7, None), (None, 30.0), (5, 30.0)])
def test_paged_kernel_vs_ref(dtype, window, softcap):
    _check_paged_kernel(dtype, window, softcap, KV=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,softcap", [
    (None, None), (7, None), (None, 30.0), (5, 30.0)])
def test_paged_kernel_vs_ref_one_kv_head(dtype, window, softcap):
    _check_paged_kernel(dtype, window, softcap, KV=1)


@pytest.mark.parametrize("seq_len", [1, 8, 17, 40])
@pytest.mark.parametrize("window", [None, 6])
def test_paged_kernel_matches_contiguous_flash(seq_len, window):
    """The property the serving path stands on: scattering a context across
    shuffled pages changes NOTHING vs contiguous flash attention."""
    H, KV, hd, T = 4, 2, 64, 8
    G = H // KV
    q, kp, vp, pt, sl = _random_paged(jax.random.key(1), 1, H, KV, hd, T,
                                      8, jnp.float32, [seq_len])
    got = np.asarray(paged_attention(q, kp, vp, pt, sl, window=window,
                                     interpret=True))[0]          # [H, hd]
    # contiguous reference: gather the pages back into [S, KV, hd], expand
    # KV heads to H, run causal flash over the real context, take the last
    # row (the broadcast q rows cannot influence it under causal masking)
    S = int(sl[0])
    ctx_k = _gather(kp, np.asarray(pt)[0])[:S]
    ctx_v = _gather(vp, np.asarray(pt)[0])[:S]
    for h in range(H):
        qh = jnp.broadcast_to(q[0, h][None, None, :], (1, S, hd))
        kh = jnp.asarray(ctx_k[:, h // G][None])
        vh = jnp.asarray(ctx_v[:, h // G][None])
        want = ref.flash_attention_ref(qh, kh, vh, causal=True,
                                       window=window)[0, -1]
        np.testing.assert_allclose(got[h], np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- cache
def _cfg():
    return dataclasses.replace(ARCHS["qwen2.5-3b"].reduced(),
                               dtype="float32")


def test_page_accounting_delta_semantics():
    cfg = _cfg()
    pb = page_bytes_for(cfg, 4)
    assert pb == 2 * cfg.n_layers * 4 * cfg.n_kv_heads \
        * cfg.resolved_head_dim * 4
    led = MemoryLedger(budget=10 * pb)
    kv = PagedKVCache(cfg, led, page_tokens=4, max_pages=16)
    assert kv.alloc("a", 6)                 # 2 pages
    assert led.resident == 2 * pb
    assert kv.extend("a", 1)                # 7 tokens: still 2 pages
    assert led.resident == 2 * pb
    assert kv.extend("a", 2)                # 9 tokens: 3rd page, delta-charge
    assert led.resident == 3 * pb
    assert kv.alloc("b", 20)                # 5 pages
    assert led.resident == 8 * pb
    assert not kv.alloc("c", 12)            # 3 pages > 2 left in budget
    assert led.resident == 8 * pb           # rejection left no residue
    kv.free("a")
    assert led.resident == 5 * pb
    assert kv.alloc("c", 12)
    kv.free("b"), kv.free("c")
    assert led.resident == 0 and kv.pages_in_use == 0
    assert len(kv._free) == 16


def test_pool_exhaustion_independent_of_ledger():
    cfg = _cfg()
    led = MemoryLedger(budget=None)         # unlimited ledger
    kv = PagedKVCache(cfg, led, page_tokens=4, max_pages=3)
    assert kv.alloc("a", 12)                # all 3 pages
    assert not kv.alloc("b", 1)             # pool, not ledger, says no
    assert not kv.extend("a", 1)
    kv.free("a")
    assert kv.alloc("b", 1)


def test_write_page_table_roundtrip_and_sentinel():
    cfg = _cfg()
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4, max_pages=8)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    kv.alloc("a", 6)
    k = rng.standard_normal((6, KV, hd)).astype(np.float32)
    v = rng.standard_normal((6, KV, hd)).astype(np.float32)
    kv.write("a", 0, 0, k, v)               # spans a page boundary
    pt, sl = kv.page_table(["a"])
    assert sl.tolist() == [6] and pt.shape == (1, 2)
    gathered = _gather(kv.k_pools[0], pt[0])[:6]
    np.testing.assert_array_equal(gathered, k)
    # sentinel page 0 is never handed out and never written
    assert 0 not in pt[0]
    assert not kv.k_pools[0][:, 0].any()
    # a second, longer sequence pads the FIRST one's table row with 0s
    kv.alloc("b", 16)
    pt2, _ = kv.page_table(["a", "b"])
    assert pt2.shape == (2, 4)
    assert (pt2[0, 2:] == 0).all()


def test_rejects_non_uniform_attention():
    mla = dataclasses.replace(ARCHS["deepseek-v2-lite-16b"].reduced(),
                              dtype="float32")
    with pytest.raises(ValueError):
        PagedKVCache(mla, MemoryLedger(None))
    ssm = dataclasses.replace(ARCHS["rwkv6-3b"].reduced(), dtype="float32")
    with pytest.raises(ValueError):
        PagedKVCache(ssm, MemoryLedger(None))


def test_for_budget_sizing():
    cfg = _cfg()
    pb = page_bytes_for(cfg, 8)
    kv = PagedKVCache.for_budget(cfg, MemoryLedger(None), 10 * pb + 5,
                                 page_tokens=8)
    assert kv.max_pages == 10


def test_ledger_never_exceeds_budget_concurrent():
    """Adversarial: admit/extend/retire hammered from several threads while
    a weight-block tenant charges the same ledger. The ledger's peak must
    stay under budget and the final state must be clean."""
    cfg = _cfg()
    pb = page_bytes_for(cfg, 4)
    budget = 12 * pb
    led = MemoryLedger(budget=budget)
    led.add("weights", 4 * pb)              # a co-resident weight block
    kv = PagedKVCache(cfg, led, page_tokens=4, max_pages=64)
    stop = threading.Event()
    errors = []

    def worker(tid):
        rng = np.random.default_rng(tid)
        try:
            for it in range(60):
                sid = (tid, it)
                if not kv.alloc(sid, int(rng.integers(1, 12))):
                    continue
                for _ in range(int(rng.integers(0, 6))):
                    if not kv.extend(sid, 1):
                        break
                kv.free(sid)
        except BaseException as e:          # noqa: BLE001
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert led.peak <= budget
    assert kv.pages_in_use == 0
    assert led.resident == 4 * pb           # only the weight block remains
    assert sorted(kv._free) == list(range(1, 65))


def test_batch_view_write_position():
    """PagedBatchView writes each sequence's new K/V at seq_len-1 and
    attends over exactly the live context."""
    cfg = _cfg()
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4, max_pages=8)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    rng = np.random.default_rng(3)
    kv.alloc("a", 5)
    k0 = rng.standard_normal((5, KV, hd)).astype(np.float32)
    v0 = rng.standard_normal((5, KV, hd)).astype(np.float32)
    kv.write("a", 0, 0, k0, v0)
    assert kv.extend("a", 1)
    view = PagedBatchView(kv, ["a"])
    q = jnp.asarray(rng.standard_normal((1, H, hd)).astype(np.float32))
    kn = rng.standard_normal((1, KV, hd)).astype(np.float32)
    vn = rng.standard_normal((1, KV, hd)).astype(np.float32)
    out = view.attend(0, q, jnp.asarray(kn), jnp.asarray(vn))
    # the new row landed at position 5
    pt, sl = kv.page_table(["a"])
    assert sl.tolist() == [6]
    np.testing.assert_array_equal(_gather(kv.k_pools[0], pt[0])[5], kn[0])
    # and the output equals the oracle over the 6-token context
    want = ref.paged_attention_ref(
        q, jnp.asarray(kv.k_pools[0]), jnp.asarray(kv.v_pools[0]),
        jnp.asarray(pt), jnp.asarray(sl))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

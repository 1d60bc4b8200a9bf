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


def _np_write(pool, pages, start, rows, T):
    """The numpy reference of a pool write: token rows [S, KV, hd] at
    positions start .. start+S of a sequence owning ``pages``, one page's
    run at a time."""
    t = 0
    while t < rows.shape[0]:
        pos = start + t
        slot = pos % T
        n = min(T - slot, rows.shape[0] - t)
        pool[:, pages[pos // T], slot:slot + n] = rows[t:t + n].swapaxes(0, 1)
        t += n


def test_write_page_table_roundtrip_and_sentinel():
    cfg = _cfg()
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4, max_pages=8)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(0)
    kv.alloc("a", 6)
    k = rng.standard_normal((6, KV, hd)).astype(np.float32)
    v = rng.standard_normal((6, KV, hd)).astype(np.float32)
    kv.append(0, kv.rows("a", 0, 6), jnp.asarray(k),
              jnp.asarray(v))               # spans a page boundary
    pt, sl = kv.page_table(["a"])
    assert sl.tolist() == [6] and pt.shape == (1, 2)
    gathered = _gather(kv.k_pools[0], pt[0])[:6]
    np.testing.assert_array_equal(gathered, k)
    np.testing.assert_array_equal(_gather(kv.v_pools[0], pt[0])[:6], v)
    # sentinel page 0 is never handed out and never written
    assert 0 not in pt[0]
    assert not np.asarray(kv.k_pools[0])[:, 0].any()
    # a second, longer sequence pads the FIRST one's table row with 0s
    kv.alloc("b", 16)
    pt2, _ = kv.page_table(["a", "b"])
    assert pt2.shape == (2, 4)
    assert (pt2[0, 2:] == 0).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["decode", "prefill"])
def test_device_append_matches_numpy_scatter(case, dtype):
    """The on-device append writes exactly what a numpy scatter writes, bit
    for bit: one row per sequence at its last position (decode), or a
    whole prompt from position 0 across page boundaries (prefill seeding,
    from the prefill's [1, S, KV, hd] K/V). Page 0 stays zero."""
    cfg = dataclasses.replace(_cfg(), dtype=dtype)
    T = 4
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=T, max_pages=12)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(dtype)
    want_k = np.zeros(kv.k_pools[1].shape, dt)
    want_v = np.zeros(kv.v_pools[1].shape, dt)
    rng = np.random.default_rng(7)
    lens = {"a": 11, "b": 3, "c": 8}
    for sid, n in lens.items():
        assert kv.alloc(sid, n)

    def page_list(sid):
        pt, _ = kv.page_table([sid])
        return pt[0]
    if case == "prefill":
        for sid, n in lens.items():
            k = rng.standard_normal((1, n, KV, hd)).astype(dt)
            v = rng.standard_normal((1, n, KV, hd)).astype(dt)
            kv.append(1, kv.rows(sid, 0, n), jnp.asarray(k), jnp.asarray(v))
            _np_write(want_k, page_list(sid), 0, k[0], T)
            _np_write(want_v, page_list(sid), 0, v[0], T)
    else:
        sids = list(lens)
        for _ in range(6):            # crosses "a"'s and "c"'s boundaries
            for sid in sids:
                assert kv.extend(sid, 1)
            k = rng.standard_normal((len(sids), KV, hd)).astype(dt)
            v = rng.standard_normal((len(sids), KV, hd)).astype(dt)
            kv.append(1, kv.last_rows(sids), jnp.asarray(k), jnp.asarray(v))
            for i, sid in enumerate(sids):
                pos = kv.seq_len(sid) - 1
                _np_write(want_k, page_list(sid), pos, k[i:i + 1], T)
                _np_write(want_v, page_list(sid), pos, v[i:i + 1], T)
    got_k, got_v = np.asarray(kv.k_pools[1]), np.asarray(kv.v_pools[1])
    assert got_k.dtype == dt
    np.testing.assert_array_equal(got_k.view(np.uint8), want_k.view(np.uint8))
    np.testing.assert_array_equal(got_v.view(np.uint8), want_v.view(np.uint8))
    assert not got_k[:, 0].any() and not got_v[:, 0].any()
    # the other layers were not touched
    assert not np.asarray(kv.k_pools[0]).any()
    written = sum(lens.values()) if case == "prefill" else 6 * len(lens)
    assert kv.rows_written == written


def test_append_donates_the_old_pools():
    """The append reuses the pools' device buffers: the arrays handed in
    are deleted (donated) and the new ones sit in the same memory, so no
    pool is copied or re-uploaded."""
    cfg = _cfg()
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4, max_pages=8)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    kv.alloc("a", 5)
    old_k, old_v = kv.k_pools[0], kv.v_pools[0]
    ptrs = (old_k.unsafe_buffer_pointer(), old_v.unsafe_buffer_pointer())
    kv.append(0, kv.rows("a", 0, 5), jnp.ones((1, 5, KV, hd)),
              jnp.ones((1, 5, KV, hd)))
    assert old_k.is_deleted() and old_v.is_deleted()
    new_k, new_v = kv.k_pools[0], kv.v_pools[0]
    assert (new_k.unsafe_buffer_pointer(),
            new_v.unsafe_buffer_pointer()) == ptrs
    assert not new_k.is_deleted() and not new_v.is_deleted()
    assert kv.upload_bytes == 0
    assert kv.device_pool_bytes == cfg.n_layers * 2 * new_k.nbytes


@pytest.mark.parametrize("case", ["decode", "prefill"])
def test_latent_append_matches_numpy_scatter(case):
    """MLA's latent rows land where the page table says, in place: a
    decode step's [B, 1, r+rd] rows, or a prefill's c_kv and k_rope side
    by side; page 0 stays zero."""
    cfg = dataclasses.replace(ARCHS["deepseek-v2-lite-16b"].reduced(),
                              dtype="float32")
    r, rd = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4, max_pages=8)
    rng = np.random.default_rng(0)
    want = np.zeros(kv.k_pools[1].shape, np.float32)
    kv.alloc("a", 7)
    kv.alloc("b", 3)
    pt, _ = kv.page_table(["a", "b"])
    if case == "decode":
        lat = rng.normal(size=(2, 1, r + rd)).astype(np.float32)
        kv.append(1, kv.last_rows(["a", "b"]), jnp.asarray(lat), None)
        want[0, pt[0, 6 // 4], 6 % 4] = lat[0, 0]
        want[0, pt[1, 2 // 4], 2 % 4] = lat[1, 0]
    else:
        c_kv = rng.normal(size=(1, 7, r)).astype(np.float32)
        k_rope = rng.normal(size=(1, 7, rd)).astype(np.float32)
        kv.ingest(1, kv.rows("a", 0, 7), {"c_kv": jnp.asarray(c_kv),
                                          "k_rope": jnp.asarray(k_rope)})
        for t in range(7):
            want[0, pt[0, t // 4], t % 4] = np.concatenate(
                [c_kv[0, t], k_rope[0, t]])
    np.testing.assert_array_equal(np.asarray(kv.k_pools[1]), want)
    assert not np.asarray(kv.k_pools[1])[:, 0].any()
    assert not np.asarray(kv.k_pools[0]).any()


def test_rejects_non_uniform_attention():
    """SSM state layers stay off the paged path; MLA is on it, with one
    latent pool a layer ([1, P+1, T, r+rd]) and a page cost of
    L * T * (r+rd) * itemsize, where GQA's is 2 * L * T * KV * hd."""
    mla = dataclasses.replace(ARCHS["deepseek-v2-lite-16b"].reduced(),
                              dtype="float32")
    kv = PagedKVCache(mla, MemoryLedger(None), page_tokens=4, max_pages=6)
    width = mla.mla.kv_lora_rank + mla.mla.qk_rope_head_dim
    assert [p.shape for p in kv.k_pools] == [(1, 7, 4, width)] * 2
    assert kv.v_pools == []
    assert kv.page_bytes == page_bytes_for(mla, 4) == 2 * 4 * width * 4
    assert kv.device_pool_bytes == 2 * 7 * 4 * width * 4
    full = ARCHS["deepseek-v2-lite-16b"]
    assert page_bytes_for(full, 16) == 27 * 16 * 576 * 2
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
    kv.append(0, kv.rows("a", 0, 5), jnp.asarray(k0), jnp.asarray(v0))
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

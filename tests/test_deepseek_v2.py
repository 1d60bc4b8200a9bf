"""DeepSeek-V2-Lite through the program, against the plain float32
reference (``deepseek_v2_reference.py``), on ``reduced()`` widths with
seeded random weights, in float32: a leading dense layer, then a MoE layer
of 16 experts (two expert groups) routing 4 per token, latent attention
with YaRN rope.

Tolerances: both sides compute in float32 from the same weights, so they
differ only in the order of summation (chunked online softmax, the latent
absorption of decode, the expert groups' accumulation). That is 1e-6 of a
logit at these widths; 1e-4 (absolute and relative) leaves two decades of
room, and a bf16 rounding anywhere on the path (8e-3 relative) breaks it.
"""
import dataclasses
import functools
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_reference as ref
from repro.configs import get_arch
from repro.core import runtime
from repro.core.cost_model import DelayModel
from repro.core.partition import PartitionPlanner
from repro.core.runtime import SwappedModel, split_units, unit_infos
from repro.core.swap_engine import MemoryLedger
from repro.models import moe as moe_mod
from repro.models.layers import rope_cos_sin_scale, yarn_inv_freq
from repro.models.attention import mla_scale
from repro.models.transformer import Model
from repro.serving.batch_engine import BatchDecodeEngine
from repro.serving.engine import Request
from repro.serving.paged_kv import PagedKVCache

TOL = dict(rtol=1e-4, atol=1e-4)
MB = 1 << 20


def small_cfg():
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, n_routed=16, top_k=4))


def make_params(cfg, seed=0):
    """``Model.init`` with the router drawn N(0, 1/d_model) like the other
    matrices: its own small init leaves the top-k to near ties."""
    params = Model(cfg).init(jax.random.key(seed))
    seg = params["segments"][1]
    r = seg["ffn"]["router"]
    seg["ffn"]["router"] = (jax.random.normal(jax.random.key(seed + 99),
                                              r.shape, jnp.float32)
                            * cfg.d_model ** -0.5)
    return params


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    params = make_params(cfg)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
               for n in (9, 14, 6)]
    return cfg, Model(cfg), params, prompts


def _swapped(model, params, workdir):
    sm = SwappedModel(model, params, workdir)
    sm.partition(budget=8 * MB, dm=DelayModel(), batch=1, seq=16)
    return sm


def test_layer_kinds_and_units(setup):
    cfg, model, params, _ = setup
    assert cfg.layer_kinds() == ("dense", "moe")
    names = [u.name for u in split_units(model, params)]
    assert names == ["embed", "layer000_dense", "layer001_moe",
                     "layer001_experts0", "layer001_experts1", "head"]
    whole = [u.name for u in split_units(model, params, expert_groups=False)]
    assert whole == ["embed", "layer000_dense", "layer001_moe", "head"]


def test_prefill_matches_reference(setup):
    """(a) ``Model.prefill`` against the reference's full forward."""
    cfg, model, params, prompts = setup
    tokens = jnp.asarray([prompts[1]], jnp.int32)
    got, _ = jax.jit(model.prefill)(params, {"tokens": tokens})
    want, _ = ref.forward(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got[0, -1]),
                               np.asarray(want[0, -1]), **TOL)


def _capture(sm, monkeypatch):
    """Every logits row the engine computes: admissions' prefill (last
    position) and each decode step's, with the batch's sequence ids."""
    seen = []
    fwd, dec = sm.forward_partial, sm.decode_step_paged

    def forward_partial(batch, **kw):
        state, stats = fwd(batch, **kw)
        seen.append(("prefill", np.asarray(batch["tokens"]),
                     np.asarray(state.logits)))
        return state, stats

    def decode_step_paged(batch, view):
        out = dec(batch, view)
        seen.append(("decode", list(view.seq_ids), np.asarray(out)))
        return out
    monkeypatch.setattr(sm, "forward_partial", forward_partial)
    monkeypatch.setattr(sm, "decode_step_paged", decode_step_paged)
    return seen


def test_served_logits_match_reference(setup, monkeypatch):
    """(b) Swapped prefill, then paged decode through ``BatchDecodeEngine``
    (latent pages, absorbed MLA in the paged kernel's oracle, expert-group
    units): every logits row against the reference's full forward over the
    prompt and the served tokens."""
    cfg, model, params, prompts = setup
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4,
                          max_pages=32)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        seen = _capture(sm, monkeypatch)
        reqs = [Request(i, p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, (5, 3, 4)))]
        for r in reqs:
            be.submit(r)
        be.run_all()
        sm.close()
    full = {r.rid: r.prompt + r.output for r in reqs}
    want = {rid: np.asarray(ref.forward(
        cfg, params, jnp.asarray([seq], jnp.int32))[0][0])
        for rid, seq in full.items()}
    steps = {rid: 0 for rid in full}
    for kind, who, logits in seen:
        if kind == "prefill":
            toks = list(who[0])
            rid = next(r for r, s in full.items() if s[:len(toks)] == toks)
            np.testing.assert_allclose(logits[0, -1],
                                       want[rid][len(toks) - 1], **TOL)
            continue
        for b, rid in enumerate(who):
            # the k-th step of a sequence decodes the token at len(prompt)+k
            pos = len(reqs[rid].prompt) + steps[rid]
            np.testing.assert_allclose(logits[b, -1], want[rid][pos], **TOL)
            steps[rid] += 1
    assert steps == {r.rid: len(r.output) - 1 for r in reqs}


def test_expert_group_units_match_whole_layers(setup, monkeypatch):
    """(c) Expert-group units against whole-layer units, prefill and paged
    decode, and a pass preempted between a layer's attention unit and its
    groups, resumed: the same steps in the same order, so the logits agree
    to float32 rounding of differently fused programs."""
    cfg, model, params, prompts = setup
    batch = {"tokens": jnp.asarray([prompts[0]], jnp.int32)}
    out = {}
    for how in ("groups", "whole"):
        with monkeypatch.context() as mp:
            if how == "whole":
                mp.setattr(runtime, "split_units", functools.partial(
                    split_units, expert_groups=False))
            with tempfile.TemporaryDirectory() as d:
                sm = _swapped(model, params, d)
                assert any(u.kind == "experts" for u in sm.units) == (
                    how == "groups")
                logits, _ = sm.forward(batch)
                kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4,
                                  max_pages=16)
                be = BatchDecodeEngine(sm, kv, max_batch=1)
                seen = _capture(sm, monkeypatch)
                req = Request(0, prompts[0], max_new_tokens=4)
                be.submit(req)
                be.run_all()
                out[how] = (np.asarray(logits), req.output,
                            [l for k, _, l in seen if k == "decode"])
                sm.close()
    np.testing.assert_allclose(out["groups"][0], out["whole"][0],
                               rtol=1e-5, atol=1e-5)
    assert out["groups"][1] == out["whole"][1]
    for a, b in zip(out["groups"][2], out["whole"][2]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    with tempfile.TemporaryDirectory() as d:
        sm = SwappedModel(model, params, d)
        names = [u.name for u in sm.units]
        cut = names.index("layer001_moe") + 1       # attention | groups
        sm.set_plan((cut, cut + 1))
        state, stats = sm.forward_partial(
            batch, should_yield=lambda s: s.next_block == 1)
        assert stats is None and isinstance(state.x, dict)   # layer state
        assert set(state.x) == {"x", "h", "top_e", "top_w", "acc"}
        state, stats = sm.forward_partial(batch, state=state)
        assert stats is not None and state.preemptions == 1
        whole, _ = sm.forward(batch)
        sm.close()
    np.testing.assert_array_equal(np.asarray(state.logits),
                                  np.asarray(whole))
    np.testing.assert_allclose(np.asarray(whole), out["groups"][0],
                               rtol=1e-5, atol=1e-5)


def test_routing_drops_no_token():
    """(d) Every token routes to the same two experts, far past the
    capacity the training dispatch allows one expert (40 slots for 64
    tokens here): the serving path computes all 64 exactly, the training
    dispatch drops 24 of them."""
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite-16b").reduced(),
                              dtype="float32")
    e = cfg.moe
    D, T = cfg.d_model, 64
    k = jax.random.split(jax.random.key(3), 6)
    base = jax.random.normal(k[0], (D,))
    h = base + 0.01 * jax.random.normal(k[1], (T, D))
    router = jnp.zeros((D, e.n_routed)).at[:, 2].set(base / D).at[:, 1].set(
        0.5 * base / D)
    p = {"router": router,
         "wi0": jax.random.normal(k[2], (e.n_routed, D, e.d_expert)) / 16,
         "wi1": jax.random.normal(k[3], (e.n_routed, D, e.d_expert)) / 16,
         "wo": jax.random.normal(k[4], (e.n_routed, e.d_expert, D)) / 16,
         "shared": {"wi0": jnp.zeros((D, e.d_shared)),
                    "wi1": jnp.zeros((D, e.d_shared)),
                    "wo": jnp.zeros((e.d_shared, D))}}
    top_e, top_w = moe_mod.route(cfg, router, h)
    assert set(np.asarray(top_e).ravel()) == {1, 2}
    capacity = max(8, int(-(-T * e.top_k // e.n_routed) * e.capacity_factor)
                   // 8 * 8)
    assert capacity < T
    x = jnp.zeros((1, T, D))
    got = moe_mod.moe_serve(cfg, p, x, h[None])[0]
    with jax.default_matmul_precision("highest"):
        want = sum(top_w[:, j, None] * jnp.stack([
            ref._swiglu(h[t], p["wi0"][i], p["wi1"][i], p["wo"][i])
            for t, i in enumerate(np.asarray(top_e[:, j]))])
            for j in range(e.top_k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    trained, _ = moe_mod.moe_train(cfg, p, h[None])
    lost = np.abs(np.asarray(trained[0] - want)).max(-1) > 1e-3
    assert lost.sum() == T - capacity


def test_yarn_numbers():
    """(e) YaRN over qk_rope_head_dim 64, theta 1e4, factor 40, original
    4096, beta 32 / 1: the ramp runs from rotation 10 to 23, the cos/sin
    scale is 1, the softmax scale 192^-0.5 * 1.26080^2."""
    cfg = get_arch("deepseek-v2-lite-16b")
    inv = yarn_inv_freq(64, 1e4, cfg.rope_scaling)
    i = np.arange(32)
    base = 1e4 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, base * (ramp / 40 + 1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert round(m, 5) == 1.26080 and round(m * m, 5) == 1.58963
    assert rope_cos_sin_scale(cfg.rope_scaling) == 1.0
    assert mla_scale(cfg) == pytest.approx(192 ** -0.5 * 1.58963, rel=1e-5)
    inv_r, c, scale = ref.yarn(cfg)
    np.testing.assert_allclose(inv, inv_r, rtol=1e-6)
    assert c == 1.0 and scale == pytest.approx(mla_scale(cfg), rel=1e-12)


def test_planner_needs_expert_groups_at_full_width():
    """(f) The benchmark's cut (layer 0 dense plus 5 MoE layers, published
    widths, bf16) from shapes alone, at 3.4x over budget with the dense
    cells' split (cache 0.2, KV 0.2, 5% reserve): a whole MoE layer
    (1,169.7 MB) outweighs the block budget, so no plan exists even at
    m=1; in expert groups (138.4 MB each) the plan keeps m=3."""
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite-16b"), n_layers=6)
    model = Model(cfg)
    struct = model.param_struct("bfloat16")
    grouped = split_units(model, struct)
    whole = split_units(model, struct, expert_groups=False)
    infos_g = unit_infos(model, grouped, 2, 64)
    infos_w = unit_infos(model, whole, 2, 64)
    size = {r.name: r.size / 1e6 for r in infos_g}
    stored = sum(r.size for r in infos_g)
    assert stored == sum(r.size for r in infos_w)
    assert stored / 1e6 == pytest.approx(6849.4, abs=0.1)    # router bf16
    assert size["layer001_moe"] == pytest.approx(62.4, abs=0.5)
    assert size["layer001_experts0"] == pytest.approx(138.4, abs=0.1)
    assert size["embed"] == pytest.approx(419.4, abs=0.1)
    assert max(size.values()) <= size["embed"] + 0.01      # the head
    budget = stored / 3.4
    blocks = budget * (1 - 0.2 - 0.2)
    whole_layer = [r.size for r in infos_w if r.name == "layer001_moe"][0]
    assert whole_layer / 1e6 == pytest.approx(1169.7, abs=0.1)
    assert whole_layer > blocks * 0.95
    with pytest.raises(ValueError, match="no feasible partition"):
        PartitionPlanner(infos_w, DelayModel(), m=3).best_partition(blocks)
    plan, _ = PartitionPlanner(infos_g, DelayModel(), m=3).best_partition(
        blocks)
    assert plan.m == 3


def test_expert_counters(setup):
    """(g) ``experts_streamed`` counts each expert of every group unit a
    pass ran; ``experts_routed`` the distinct (MoE layer, expert) pairs
    some token of the pass routed to, as the reference routes them."""
    cfg, model, params, prompts = setup
    with tempfile.TemporaryDirectory() as d:
        sm = _swapped(model, params, d)
        kv = PagedKVCache(cfg, MemoryLedger(None), page_tokens=4,
                          max_pages=32)
        be = BatchDecodeEngine(sm, kv, max_batch=2)
        reqs = [Request(i, prompts[i], max_new_tokens=3) for i in (0, 1)]
        for r in reqs:
            be.submit(r)
        be.run_all()
        st = be.stats()
        sm.close()
    routes = {r.rid: ref.forward(cfg, params, jnp.asarray(
        [r.prompt + r.output], jnp.int32))[1][1][0] for r in reqs}
    passes = 0
    want = 0
    for r in reqs:                                  # the two admissions
        want += len(set(np.asarray(routes[r.rid][:len(r.prompt)]).ravel()))
        passes += 1
    for t in be.trace:                              # the decode steps
        if not t.batch:
            continue
        passes += 1
        picked = set()
        for rid in t.batch:
            n = sum(1 for u in be.trace[:t.step] if rid in u.batch)
            picked |= set(np.asarray(
                routes[rid][len(reqs[rid].prompt) + n]).ravel())
        want += len(picked)
    assert st["experts_streamed"] == passes * cfg.moe.n_routed
    assert st["experts_routed"] == want

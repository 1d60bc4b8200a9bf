"""The ``mla_moe`` family (DeepSeek-V2-Lite): its reference is the program
in float32, its counts are the hand-worked numbers, and its cell runs
through the harness at a tiny size. On the CPU; run by hand:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
import run

CELL = "deepseek-v2-lite.chat"


@pytest.fixture(autouse=True)
def _reduced(monkeypatch):
    tiny.reduce_archs(monkeypatch)


def test_reference_is_the_program_in_float32():
    """The family's weights in the program's layout, through the program's
    whole-model prefill in float32, against the family's reference: equal
    to float32 rounding (4e-6 of a logit here); in bf16 they part."""
    import serving
    from repro.models.transformer import Model
    c = tiny.shrink(run.load_cell(CELL))
    mc = serving.program_config(c.cfg, c.family)
    params = c.family.make_params(c.cfg["model"], 7)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, mc.vocab_size, (2, 40)), jnp.int32)
    rows = jnp.asarray([[39], [39]], jnp.int32)
    ref = np.asarray(c.family.Reference(c.cfg["model"]).logits(
        params, toks, rows))[:, 0]
    m = Model(dataclasses.replace(mc, dtype="float32"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    got = np.asarray(jax.jit(m.prefill)(p32, {"tokens": toks})[0])[:, 0]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_counts_at_published_width():
    """Hand-worked: one decode token of the 6-layer cut is 1.41 GFLOP
    (13.8 M attention weights a layer, the dense layer's 67.2 M, a MoE
    layer's router, 6 experts and shared experts 69.3 M, the head 210 M,
    all twice); a decode step of 8 tokens reads each MoE layer's 1,107.3
    MB of routed experts once."""
    c = run.load_cell(CELL)
    cfg = json.loads((tiny.BENCH / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    d = c.family.dims(cfg["model"])
    attn = 2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 * 2 \
        + 16 * 128 * 2048
    moe = 2048 * 64 + 3 * 2048 * 1408 * 6 + 3 * 2048 * 2816
    want = 2.0 * (6 * attn + 3 * 2048 * 10944 + 5 * moe + 2048 * 102400)
    ctx = 300
    want += 6 * 2.0 * ctx * 16 * (576 + 512)
    assert c.family.decode_flops(d, ctx) == pytest.approx(want, rel=1e-12)
    flops, nbytes = c.family.kernel_cost(d, "expert_group", [300] * 8)
    assert nbytes == pytest.approx(5 * (2 * 64 * 3 * 2048 * 1408
                                        + 8 * 8 * 2048 * 10))
    assert flops == pytest.approx(5 * 2.0 * 8 * 6 * 3 * 2048 * 1408)
    f, b = c.family.kernel_cost(d, "paged_attention", [100, 200])
    assert f == 6 * 2.0 * 16 * (576 + 512) * 300
    assert b == 6 * 2 * (300 * 576 + 2 * 2 * 16 * 576)
    assert c.family.kernel_cost(d, "dequant", [1]) is None


def test_published_keys_at_the_top_level_match_the_model_block():
    """The file states the published config twice: at its top level, key
    for key as the source has it, and in the ``model`` block the harness
    reads. The two agree everywhere, and only ``reduced`` keys differ
    from the source."""
    cfg = json.loads((tiny.BENCH / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    assert {k: cfg[k] for k in cfg["model"]} == cfg["model"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6
    assert cfg["first_k_dense_replace"] == 1
    assert cfg["rope_scaling"]["factor"] == 40


def test_cell_runs_correct_with_its_readers():
    """The cell at a tiny size: correct, and the new readers read."""
    c = tiny.shrink(run.load_cell(CELL))
    res = run.run_cell(c, 2**31 + 23, 3.0, False, tiny.PEAK,
                       log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["compiled_in_window"] == 0
    names = {m["name"] for m in c.metrics}
    assert {"routed_expert_share.chat", "expert_group_roofline",
            "paged_attention_roofline"} <= names

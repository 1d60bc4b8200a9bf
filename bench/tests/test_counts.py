"""The counts of the qwen configuration's family, as the harness loads it
(``bench/families/dense_gqa.py``), and ``bench/counts.py``'s roofline,
against FLOPs and bytes worked out by hand."""
from __future__ import annotations

import pytest

import tiny  # puts bench/ on the path
from counts import least_seconds

_QWEN = tiny.run.load_cell("qwen2.5-3b.chat")
counts = _QWEN.family            # the family the qwen file resolves to
QWEN = counts.dims(_QWEN.cfg["model"])
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_layer_weights_of_qwen():
    # q 2048x2048, k and v 2048x256, o 2048x2048, MLP 3 x 2048x11008
    assert counts.layer_matmul_params(QWEN) == (
        4194304 + 2 * 524288 + 4194304 + 3 * 22544384)


def test_decode_token_of_qwen():
    ctx = 100
    want = (2 * 36 * 77070336              # every layer's matmuls
            + 4 * 100 * 16 * 128 * 36      # QK^T and PV over 100 tokens
            + 2 * 2048 * 151936)           # the head, once
    assert counts.decode_flops(QWEN, ctx) == pytest.approx(want, rel=1e-12)


def test_prefill_of_qwen():
    S = 4
    want = (2 * 36 * 77070336 * 4 + 4 * 4 * 4 * 16 * 128 * 36 / 2
            + 2 * 2048 * 151936)
    assert counts.prefill_flops(QWEN, S) == pytest.approx(want, rel=1e-12)


def test_window_caps_the_attended_context():
    d = dict(QWEN, window=64)
    assert (counts.decode_flops(d, 1000) - counts.decode_flops(d, 64)) == 0


def test_paged_attention_call():
    flops, nbytes = counts.paged_attention_cost(QWEN, [10, 20])
    # 4 * H * hd per context token; K and V of 30 tokens over 2 KV heads,
    # q and out once per head per sequence, 2 bytes each
    assert flops == 4 * 16 * 128 * 30
    assert nbytes == 2 * (2 * 30 * 2 * 128) + 2 * 2 * (2 * 16 * 128)
    assert least_seconds(flops, nbytes, PEAK) == pytest.approx(
        nbytes / 819e9)

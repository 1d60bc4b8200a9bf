"""Config registry invariants: assignments, citations, shapes, reductions."""
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, SHAPES, applicable, get_arch, get_shape

ASSIGNED = {
    "granite-20b": dict(n_layers=52, d_model=6144, n_heads=48, n_kv_heads=1,
                        d_ff=24576, vocab_size=49152),
    "rwkv6-3b": dict(n_layers=32, d_model=2560, d_ff=8960, vocab_size=65536),
    "qwen2-vl-72b": dict(n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                         d_ff=29568, vocab_size=152064),
    "qwen2.5-3b": dict(n_layers=36, d_model=2048, n_heads=16, n_kv_heads=2,
                       d_ff=11008, vocab_size=151936),
    "zamba2-7b": dict(n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
                      d_ff=14336, vocab_size=32000),
    "hubert-xlarge": dict(n_layers=48, d_model=1280, n_heads=16,
                          n_kv_heads=16, d_ff=5120, vocab_size=504),
    "h2o-danube-3-4b": dict(n_layers=24, d_model=3840, n_heads=32,
                            n_kv_heads=8, d_ff=10240, vocab_size=32000),
    "gemma2-9b": dict(n_layers=42, d_model=3584, n_heads=16, n_kv_heads=8,
                      d_ff=14336, vocab_size=256000),
    "deepseek-v2-lite-16b": dict(n_layers=27, d_model=2048, n_heads=16,
                                 d_ff=10944, vocab_size=102400,
                                 first_k_dense=1),
    "llama4-scout-17b-a16e": dict(n_layers=48, d_model=5120, n_heads=40,
                                  n_kv_heads=8, d_ff=8192, vocab_size=202048),
}


def test_all_assigned_archs_present():
    assert set(ASSIGNED) == set(ARCHS)


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_exact_assigned_numbers(arch):
    cfg = get_arch(arch)
    for k, v in ASSIGNED[arch].items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    assert cfg.source, f"{arch} missing citation"


def test_family_traits():
    assert get_arch("rwkv6-3b").ssm.kind == "rwkv6"
    assert get_arch("zamba2-7b").ssm.kind == "mamba2"
    assert get_arch("zamba2-7b").ssm.d_state == 64
    assert get_arch("zamba2-7b").hybrid_attn_every == 6
    assert get_arch("deepseek-v2-lite-16b").mla.kv_lora_rank == 512
    m = get_arch("deepseek-v2-lite-16b").moe
    assert (m.n_routed, m.top_k, m.n_shared) == (64, 6, 2)
    assert (m.d_expert, m.d_shared, m.norm_topk_prob) == (1408, 2816, False)
    kinds = get_arch("deepseek-v2-lite-16b").layer_kinds()
    assert kinds[0] == "dense" and set(kinds[1:]) == {"moe"}
    assert get_arch("deepseek-v2-lite-16b").rope_scaling.factor == 40.0
    m = get_arch("llama4-scout-17b-a16e").moe
    assert (m.n_routed, m.top_k, m.n_shared) == (16, 1, 1)
    assert get_arch("gemma2-9b").attn_logit_softcap == 50.0
    assert get_arch("gemma2-9b").layer_pattern == "alt_local_global"
    assert get_arch("h2o-danube-3-4b").sliding_window == 4096
    assert get_arch("qwen2-vl-72b").rope_type == "mrope"
    assert get_arch("qwen2.5-3b").attn_bias
    assert get_arch("hubert-xlarge").is_encoder


def test_shapes_exact():
    assert (SHAPES["train_4k"].seq_len, SHAPES["train_4k"].global_batch) == (4096, 256)
    assert (SHAPES["prefill_32k"].seq_len, SHAPES["prefill_32k"].global_batch) == (32768, 32)
    assert (SHAPES["decode_32k"].seq_len, SHAPES["decode_32k"].global_batch) == (32768, 128)
    assert (SHAPES["long_500k"].seq_len, SHAPES["long_500k"].global_batch) == (524288, 1)


def test_reduced_limits():
    for cfg in ARCHS.values():
        r = cfg.reduced()
        assert r.n_layers <= 4 and r.d_model <= 512
        if r.moe:
            assert r.moe.n_routed <= 4
        # reduced keeps the family
        assert r.family == cfg.family


def test_combination_counts():
    # 34 after llama4 gained iRoPE chunked attention (long_500k now runs);
    # 6 principled skips remain (DESIGN.md §5)
    runs = sum(applicable(ARCHS[a], SHAPES[s]) for a in ARCHS for s in SHAPES)
    assert runs == 34 and len(ARCHS) * len(SHAPES) == 40

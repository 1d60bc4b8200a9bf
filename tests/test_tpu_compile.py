"""The main-path kernels compile for a TPU v5e at qwen2.5-3b's widths.

Compiled ahead of time for a described (not attached) ``v5e:2x2`` topology:
the TPU compiler refuses what interpret mode accepts (blocks off the
(8, 128) tiling, more scoped VMEM than a kernel may use), so these guard
every change at no chip time. The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.dequant import dequant_int8
from repro.kernels.paged_attention import paged_attention
from repro.kernels.swap_linear_q import swap_linear_q

CFG = get_arch("qwen2.5-3b")
D, F, V, HD = CFG.d_model, CFG.d_ff, CFG.vocab_size, CFG.resolved_head_dim


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library logs to a fixed directory under /tmp unless told
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu")))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("KV", [1, 2, 8])
def test_paged_attention_compiles(one_chip, KV):
    B, G, T, P, NP = 8, 8, 16, 64, 8
    _compile(paged_attention, one_chip,
             ((B, KV * G, HD), jnp.bfloat16),
             ((KV, P + 1, T, HD), jnp.bfloat16),
             ((KV, P + 1, T, HD), jnp.bfloat16),
             ((B, NP), jnp.int32), ((B,), jnp.int32))


def test_paged_attention_compiles_for_latent_pages(one_chip):
    """DeepSeek-V2-Lite's absorbed MLA decode: 16 query heads over one
    latent "KV head" of width 576, the pool as both keys and values."""
    m = get_arch("deepseek-v2-lite-16b").mla
    W = m.kv_lora_rank + m.qk_rope_head_dim
    B, H, T, P, NP = 8, 16, 16, 64, 8
    _compile(lambda q, pool, pt, sl: paged_attention(q, pool, pool, pt, sl),
             one_chip, ((B, H, W), jnp.bfloat16),
             ((1, P + 1, T, W), jnp.bfloat16),
             ((B, NP), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("bits,N", [(8, F), (4, F), (8, V), (4, V)],
                         ids=["int8-mlp", "int4-mlp", "int8-head",
                              "int4-head"])
def test_swap_linear_q_compiles(one_chip, bits, N):
    for M in (1, 512):
        _compile(lambda x, q, s: swap_linear_q(x, q, s, bits=bits), one_chip,
                 ((M, D), jnp.bfloat16), ((D * bits // 8, N), jnp.int8),
                 ((N,), jnp.float32))


def test_dequant_int8_compiles(one_chip):
    for out in (jnp.bfloat16, jnp.float32):
        _compile(lambda v, s: dequant_int8(v, s, out), one_chip,
                 ((D, F), jnp.int8), ((F,), jnp.float32))

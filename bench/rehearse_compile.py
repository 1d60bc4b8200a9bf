#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py [config]

(default ``h2o-danube-3-4b``), a configuration of the ``dense_gqa``
family, with the file's ``program`` cut applied. No chip is needed: the
TPU compiler builds each program for a v5e that is described, not
attached, and refuses what the chip's compiler would refuse (a kernel
tile it cannot lower, more memory than the chip has). Compiled at the
configuration's published widths, at the shapes its chat cell sends:

* the swapped prefill layer (``apply_layer_jit``) at the shortest and the
  longest prompt of the mix;
* the paged decode layer's two programs around the page append
  (``_paged_qkv``, ``_paged_out``) and the head, at batch 1 and 8;
* the ``paged_attention`` kernel, forced to its Mosaic lowering, at batch
  8 and the mix's most pages, over the KV pool the budget sizes.

Each line names the program, whether a Mosaic kernel is in it, and the
device memory the compiler plans for it. It runs nothing.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
from jax.experimental import topologies             # noqa: E402
from jax.sharding import SingleDeviceSharding       # noqa: E402

import family                                       # noqa: E402
import traffic                                      # noqa: E402


def main(name: str = "h2o-danube-3-4b") -> int:
    from serving import program_config
    from repro.core.runtime import head_logits
    from repro.kernels.paged_attention import paged_attention
    from repro.models import attention as attn_mod
    from repro.models.transformer import (_paged_out, _paged_qkv,
                                          apply_layer_jit)
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    fam = family.of(cfg, BENCH.parent)
    mc = program_config(cfg, fam)
    d = fam.dims(cfg["model"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bf16, i32 = jnp.bfloat16, jnp.int32

    def sds(shape, dt=bf16):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    layer = {}
    for path, (shape, _, _) in fam.param_shapes(d).items():
        if path.startswith("segments/0/"):
            node = layer
            parts = path.split("/")[2:]
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = sds(shape[1:])
    D, H, KV, hd, V = d["D"], d["H"], d["KV"], d["hd"], d["V"]
    local = mc.is_local_layer(0)
    T = cfg["deployment"]["runtime"]["page_tokens"]
    stored = sum(int(jnp.prod(jnp.asarray(s))) * 2
                 for s, _, _ in fam.param_shapes(d).values())
    stored += D * V * 2 if d["tied"] else 0
    budget = stored / cfg["budget_ratio"]
    pages = int(budget * cfg["deployment"]["runtime"]["kv_frac"]
                // (2 * d["L"] * T * KV * hd * 2))
    NP = -(-traffic.max_context(mix) // T)
    ok = True

    def report(what, fn, *args, **kw):
        nonlocal ok
        try:
            lowered = jax.jit(fn, **kw).lower(*args)
            mosaic = "tpu_custom_call" in lowered.as_text()
            mem = lowered.compile().memory_analysis()
            temp = getattr(mem, "temp_size_in_bytes", -1)
            print(f"{what}: compiled; mosaic={mosaic}; temp "
                  f"{temp / 1e6:.1f} MB", flush=True)
        except Exception as e:          # noqa: BLE001 — reported, fails
            ok = False
            print(f"{what}: REFUSED: {type(e).__name__}: {e}"[:2000],
                  flush=True)

    sizes = traffic.block_sizes(mix)
    for S in (min(p for p, _ in sizes), max(p for p, _ in sizes)):
        report(f"prefill layer S={S}",
               lambda p, x, pos: apply_layer_jit(mc, "dense", p, x, pos,
                                                 local, None, None,
                                                 "prefill"),
               layer, sds((1, S, D)), sds((1, S), i32))
    for B in (1, 8):
        report(f"decode qkv B={B}",
               lambda p, x, pos: _paged_qkv(mc, p, x, pos),
               layer, sds((B, 1, D)), sds((B, 1), i32))
        report(f"decode out B={B}",
               lambda p, x, a: _paged_out(mc, "dense", p, x, a),
               layer, sds((B, 1, D)), sds((B, 1, H, hd)))
        report(f"head B={B}",
               lambda n, w, h: head_logits(mc, n, w, h),
               sds((D,)), sds((D, V)), sds((B, 1, D)))
    report(f"paged_attention B=8 KV={KV} hd={hd} pages={NP} pool={pages}",
           lambda q, kp, vp, pt, sl: paged_attention(
               q, kp, vp, pt, sl, scale=attn_mod.attn_scale(mc),
               window=attn_mod.paged_window(mc, local),
               softcap=mc.attn_logit_softcap, interpret=False),
           sds((8, H, hd)), sds((KV, pages + 1, T, hd)),
           sds((KV, pages + 1, T, hd)), sds((8, NP), i32), sds((8,), i32))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))

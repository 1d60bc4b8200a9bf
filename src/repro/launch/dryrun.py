import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# The lines above MUST run before any jax import (platform and device count
# lock at first init). The dry-run only lowers for 512 placeholder host
# devices, so it never opens an accelerator, even on a host that has one.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, applicable, get_arch, get_shape  # noqa: E402
from repro.distributed.sharding import filter_spec, set_mesh  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.transformer import (Model, input_pspecs, input_specs)  # noqa: E402
from repro.training.optimizer import OptConfig  # noqa: E402
from repro.training.train_loop import make_train_step, train_state_specs  # noqa: E402

# --------------------------------------------------------------------------
# HLO collective parsing: cost_analysis() has no collective bytes, so we sum
# operand/result sizes of every collective op in the post-SPMD module.
# --------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: op count and result bytes (per device)."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", s)
        if not m:
            continue
        rhs = m.group(1)
        opm = re.match(r"^((?:\([^)]*\)|\S+))\s+([\w\-]+)\(", rhs)
        if not opm:
            continue
        shape_txt, opname = opm.group(1), opm.group(2)
        # normalize fused variants like all-reduce-start
        base = None
        for k in _COLLECTIVES:
            if opname == k or opname.startswith(k + "-start"):
                base = k
                break
        if base is None:
            continue
        out[base]["count"] += 1
        out[base]["bytes"] += _shape_bytes(shape_txt)
    return out


# --------------------------------------------------------------------------
def _shardings(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, filter_spec(s, mesh)),
        tree_specs, is_leaf=lambda x: isinstance(x, P))


from repro.configs.flops import analytic_flops_per_device  # noqa: E402


def build_lowering(arch: str, shape_name: str, mesh, donate: bool = True):
    """Returns (lowered, meta) for the (arch, shape) combination."""
    import repro.models.transformer as tmod
    shape_cfg = get_shape(shape_name)
    # honest HLO accounting for inference; train keeps the rolled scan
    # (see analytic_flops_per_device)
    tmod.LAYER_SCAN_UNROLL = shape_cfg.mode != "train"
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    model = Model(cfg)
    batch_struct = input_specs(cfg, shape)
    batch_shard = _shardings(input_pspecs(cfg, shape, mesh), mesh)

    if shape.mode == "train":
        params = model.param_struct()            # fp32 master
        state = {"params": params,
                 "mu": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
                 "nu": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_shard = _shardings(train_state_specs(model), mesh)
        step = make_train_step(model, OptConfig())
        fn = jax.jit(step, in_shardings=(state_shard, batch_shard),
                     donate_argnums=(0,) if donate else ())
        lowered = fn.lower(state, batch_struct)
    elif shape.mode == "prefill":
        params = model.param_struct(cfg.dtype)   # serving weights bf16
        pshard = _shardings(model.param_specs(), mesh)
        fn = jax.jit(model.prefill, in_shardings=(pshard, batch_shard))
        lowered = fn.lower(params, batch_struct)
    else:  # decode
        params = model.param_struct(cfg.dtype)
        pshard = _shardings(model.param_specs(), mesh)
        cache = model.cache_struct(shape)
        cshard = _shardings(model.cache_specs(shape, mesh), mesh)
        fn = jax.jit(model.decode_step,
                     in_shardings=(pshard, cshard, batch_shard),
                     donate_argnums=(1,) if donate else ())
        lowered = fn.lower(params, cache, batch_struct)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(model.param_struct()))
    n_dev = int(np.prod(mesh.devices.shape))
    return lowered, {"n_params": n_params, "mode": shape.mode,
                     "n_devices": n_dev,
                     "flops_analytic_per_dev":
                         analytic_flops_per_device(cfg, shape, n_dev),
                     "tokens": shape.global_batch * (1 if shape.mode == "decode"
                                                     else shape.seq_len)}


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: Optional[str] = None, verbose: bool = True,
            flash_decode: bool = False, tag_suffix: str = "") -> Dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    if flash_decode:
        from repro.models import attention as attn_mod
        shape_cfg = get_shape(shape_name)
        if shape_cfg.global_batch == 1:
            attn_mod.SHARDED_DECODE_AXIS = ("pod", "data", "model")
        else:
            attn_mod.SHARDED_DECODE_AXIS = ("model",)
    t0 = time.time()
    try:
        lowered, meta = build_lowering(arch, shape_name, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        mem_d = {}
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                if hasattr(mem, k):
                    mem_d[k] = int(getattr(mem, k))
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost_d = {k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float)) and (
                      k in ("flops", "bytes accessed", "optimal_seconds")
                      or k.startswith("bytes accessed"))}
        coll = parse_collectives(compiled.as_text())
        result = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "ok", "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory_analysis": mem_d, "cost_analysis": cost_d,
            "collectives": coll, **meta,
        }
    except Exception as e:  # noqa: BLE001 — recorded, not swallowed silently
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "status": "error", "error": f"{type(e).__name__}: {e}"}
    finally:
        set_mesh(None)
        if flash_decode:
            from repro.models import attention as attn_mod
            attn_mod.SHARDED_DECODE_AXIS = None
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{result['mesh']}{tag_suffix}.json"
        with open(os.path.join(out_dir, tag), "w") as fh:
            json.dump(result, fh, indent=1)
    if verbose:
        if result["status"] == "ok":
            ca = result["cost_analysis"]
            print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: OK "
                  f"flops/dev={ca.get('flops', 0):.3e} "
                  f"compile={result['compile_s']}s", flush=True)
            print(f"  memory_analysis: {result['memory_analysis']}", flush=True)
        else:
            print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: "
                  f"FAILED {result['error']}", flush=True)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every applicable (arch x shape) on this mesh")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--flash-decode", action="store_true",
                    help="§Perf variant: shard_map flash-decoding over the "
                         "sequence-sharded KV cache")
    ap.add_argument("--windowed-kv", action="store_true",
                    help="§Perf variant: ring-buffer KV cache for SWA archs")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="§Perf variant: sequence-parallel residual stream "
                         "(train memory)")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    args = ap.parse_args()
    if args.windowed_kv:
        import repro.models.transformer as _t
        _t.WINDOWED_KV_CACHE = True
    if args.seq_parallel:
        import repro.models.transformer as _t
        _t.SEQ_PARALLEL_RESIDUAL = True

    combos = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    n_ok = n_skip = n_err = 0
    for a, s in combos:
        if not applicable(ARCHS[a], SHAPES[s]):
            print(f"[dryrun] {a} x {s}: SKIP (per DESIGN.md §5)", flush=True)
            n_skip += 1
            continue
        tag = os.path.join(args.out, f"{a}__{s}__{mesh_tag}.json")
        if args.skip_existing and os.path.exists(tag):
            with open(tag) as fh:
                if json.load(fh).get("status") == "ok":
                    n_ok += 1
                    continue
        r = run_one(a, s, args.multi_pod, args.out,
                    flash_decode=args.flash_decode, tag_suffix=args.tag)
        if r["status"] == "ok":
            n_ok += 1
        else:
            n_err += 1
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} failed",
          flush=True)
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

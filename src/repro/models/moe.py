"""Mixture-of-Experts: a softmax top-k router, shared experts, and two
ways to apply the routed experts.

Serving (prefill and decode) routes drop-free: every token reaches each of
its top-k experts at any batch, with shapes that do not depend on the
routing. The routed experts are applied one group of
:data:`EXPERT_GROUP` at a time (:func:`routed_group`): every token against
each expert of the group, the gate zero where the token did not route
there. A layer is then ``begin`` (route, shared experts), one ``group``
step per expert group, ``end`` (the residual); the whole-layer path
(:func:`moe_serve`) and the swapped executor's expert-group units
(``core/runtime.py``) run those same steps, so there is one routing path.

Training keeps the capacity-based sort/gather dispatch with its
Switch-style load-balance loss (:func:`moe_train`): expert-parallel
friendly, the [E, C, D] buffer sharded over the "model" (experts) axis;
tokens past an expert's capacity are dropped there.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ParamDef, maybe_constrain


def moe_defs(cfg: ModelConfig) -> dict:
    e = cfg.moe
    D = cfg.d_model
    # Expert weights: EP over "model" on E; the FSDP ("data") shard lives on
    # the EXPERT-HIDDEN dim (F), not on D — contracting over an FSDP-sharded
    # D would force an [E,C,F]-sized activation all-reduce per matmul (§Perf
    # hypothesis B, confirmed: 1.5 TB/step on llama4 prefill). With F sharded,
    # wi0/wi1 contract over a whole D, the gated product stays F-sharded, and
    # only wo's output pays one (much smaller) [E,C,D] reduction.
    d = {
        "router": ParamDef((D, e.n_routed), ("residual", None), init="small",
                           dtype="float32"),
        "wi0": ParamDef((e.n_routed, D, e.d_expert), ("experts", None, "residual")),
        "wi1": ParamDef((e.n_routed, D, e.d_expert), ("experts", None, "residual")),
        "wo": ParamDef((e.n_routed, e.d_expert, D), ("experts", "residual", None)),
    }
    if e.n_shared:
        ds = e.d_shared or e.d_expert * e.n_shared
        d["shared"] = {
            "wi0": ParamDef((D, ds), ("residual", "tp")),
            "wi1": ParamDef((D, ds), ("residual", "tp")),
            "wo": ParamDef((ds, D), ("tp", "residual")),
        }
    return d


# Routed experts per expert group: the swap unit of a MoE layer's experts
# and the step of its routed-expert loop. Eight of DeepSeek-V2-Lite's 64
# (138.4 MB in bf16) make a unit small enough that three consecutive units
# fit the block budget of a 3.4x-over-budget plan (a whole layer, 1,169.7 MB,
# does not); eight groups a layer keep the dispatches per layer few. At
# decode batch 8 a group of 8 almost always holds a routed expert, so
# smaller groups would not stream fewer bytes.
EXPERT_GROUP = 8


def expert_groups(n_routed: int):
    """(lo, n) of each expert group of a layer with ``n_routed`` experts."""
    g = min(EXPERT_GROUP, n_routed)
    return [(lo, min(g, n_routed - lo)) for lo in range(0, n_routed, g)]


def _router_probs(h: jax.Array, router: jax.Array) -> jax.Array:
    """Softmax over the router's logits, in float32: h [T, D] -> [T, E]."""
    logits = jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def route(cfg: ModelConfig, router: jax.Array, h: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """Greedy top-k over the softmax: h [T, D] -> (top_e [T, K] int32,
    top_w [T, K] float32), the weights renormalised to sum to 1 only where
    ``norm_topk_prob`` is set."""
    e = cfg.moe
    top_w, top_e = jax.lax.top_k(_router_probs(h, router), e.top_k)
    if e.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_e.astype(jnp.int32), top_w


def routed_group(w: dict, h: jax.Array, top_e: jax.Array, top_w: jax.Array,
                 lo) -> jax.Array:
    """What experts [lo, lo + n) add for each token, float32 [T, D]: ``w``
    holds their stacked SwiGLU weights (wi0, wi1 [n, D, F], wo [n, F, D]),
    h [T, D] the tokens' normed input, (top_e, top_w) their routing. Every
    token runs through every expert of the group and is weighted by its
    gate there, zero where it did not route: no token is dropped and no
    shape depends on the routing. ``lo`` may be traced."""
    n = w["wi0"].shape[0]
    ids = lo + jnp.arange(n, dtype=top_e.dtype)                   # [n]
    gate = jnp.sum(jnp.where(top_e[:, :, None] == ids, top_w[:, :, None],
                             0.0), axis=1)                        # [T, n]
    dt = w["wi0"].dtype
    hx = h.astype(dt)
    a = jnp.einsum("td,edf->etf", hx, w["wi0"])
    b = jnp.einsum("td,edf->etf", hx, w["wi1"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * b, w["wo"])
    # the gate applied elementwise in float32 (a matmul would round it)
    return jnp.sum(y.astype(jnp.float32) * gate.T[:, :, None], axis=0)


def routed_count(top_e: jax.Array, lo, n: int) -> jax.Array:
    """How many of experts [lo, lo + n) some token routed to (int32)."""
    ids = lo + jnp.arange(n, dtype=top_e.dtype)
    return jnp.sum(jnp.any(top_e.reshape(-1)[:, None] == ids, axis=0)
                   ).astype(jnp.int32)


def shared_apply(sp: dict, h: jax.Array) -> jax.Array:
    """The merged shared experts, a dense SwiGLU (through ``linear``, so a
    quantized-resident weight streams through the fused kernel)."""
    from repro.models.layers import linear
    return linear(linear(h, sp["wi0"], act="silu") * linear(h, sp["wi1"]),
                  sp["wo"])


# ------------------------------------------------------------------ serving
def begin(cfg: ModelConfig, p: dict, x: jax.Array, h: jax.Array
          ) -> Dict[str, jax.Array]:
    """A MoE layer's state before its routed experts: ``x`` [B, S, D] is the
    residual after attention, ``h`` its normed input to the experts. Routes
    the tokens and starts the float32 accumulator at the shared experts'
    output. ``p`` is the layer's ``ffn`` params (the routed stacks may be
    absent)."""
    B, S, D = x.shape
    hf = h.reshape(B * S, D)
    top_e, top_w = route(cfg, p["router"], hf)
    acc = jnp.zeros((B * S, D), jnp.float32)
    if cfg.moe.n_shared:
        acc = acc + shared_apply(p["shared"], hf).astype(jnp.float32)
    return {"x": x, "h": hf, "top_e": top_e, "top_w": top_w, "acc": acc}


def group(carry: Dict[str, jax.Array], w: dict, lo) -> Dict[str, jax.Array]:
    """The state with experts [lo, lo + n) of ``w`` added."""
    acc = carry["acc"] + routed_group(w, carry["h"], carry["top_e"],
                                      carry["top_w"], lo)
    return dict(carry, acc=acc)


def end(carry: Dict[str, jax.Array]) -> jax.Array:
    """The layer's output: the residual plus the accumulated experts,
    rounded once."""
    x = carry["x"]
    return (x.astype(jnp.float32)
            + carry["acc"].reshape(x.shape)).astype(x.dtype)


def moe_serve(cfg: ModelConfig, p: dict, x: jax.Array, h: jax.Array
              ) -> jax.Array:
    """A whole MoE layer after attention, drop-free: ``begin``, every expert
    group in order, ``end`` (the executor's expert-group units run the same
    steps one unit at a time)."""
    carry = begin(cfg, p, x, h)
    for lo, n in expert_groups(cfg.moe.n_routed):
        w = {k: p[k][lo:lo + n] for k in ("wi0", "wi1", "wo")}
        carry = group(carry, w, lo)
    return end(carry)


# ------------------------------------------------------------------ training
def moe_train(cfg: ModelConfig, p: dict, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D] -> (y [B,S,D], aux_loss scalar): capacity dispatch with
    the Switch-style load-balance loss, for the training path only."""
    e = cfg.moe
    capacity_factor = e.capacity_factor
    B, S, D = x.shape
    T = B * S
    E, K = e.n_routed, e.top_k
    xf = x.reshape(T, D)

    probs = _router_probs(xf, p["router"])                     # [T,E]
    top_e, top_w = route(cfg, p["router"], xf)                 # [T,K]

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)      # [T,K,E]
    f = one_hot.sum((0, 1)) / (T * K)
    pbar = probs.mean(0)
    aux = e.aux_loss_weight * E * jnp.sum(f * pbar)

    C = max(8, int(-(-T * K // E) * capacity_factor) // 8 * 8)  # per-expert slots
    flat_tok = jnp.repeat(jnp.arange(T), K)                    # [T*K]
    flat_e = top_e.reshape(-1)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    s_tok, s_e, s_w = flat_tok[order], flat_e[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K) - starts[s_e]
    ok = pos < C
    slot = jnp.where(ok, s_e * C + pos, E * C)                 # OOB -> dropped

    buf = jnp.zeros((E * C, D), x.dtype).at[slot].set(
        xf[s_tok], mode="drop")
    h = buf.reshape(E, C, D)
    h = maybe_constrain(h, P("model", None, None))
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["wi0"]))
    up = jnp.einsum("ecd,edf->ecf", h, p["wi1"])
    out = jnp.einsum("ecf,efd->ecd", gate * up, p["wo"])
    out = maybe_constrain(out, P("model", None, None))

    y_sorted = out.reshape(E * C, D)[jnp.minimum(slot, E * C - 1)]
    contrib = y_sorted * (s_w * ok)[:, None]
    y = jnp.zeros((T, D), x.dtype).at[s_tok].add(contrib.astype(x.dtype))

    if e.n_shared:
        y = y + shared_apply(p["shared"], xf)
    return y.reshape(B, S, D), aux

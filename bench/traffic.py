"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) gives

* ``requests``: ``"generate"`` (a prompt and greedy output tokens) or
  ``"prefill"`` (a prompt, answered with its last position's logits);
* ``arrivals``: ``{"process": "closed", "clients": n}`` for clients that
  each wait for their reply and send the next at once, or
  ``{"process": "poisson", "rate": r}`` for requests sent on a seeded
  schedule of ``r`` per second whether or not earlier ones finished;
* ``prompt`` and ``output``: length distributions (below);
* ``block``: how many requests one block of sizes holds.

Every seed gets the same sizes: block after block, each holding the same
multiset of (prompt, output) lengths, stratified over the distributions
(quantiles at the block's midpoints, paired by a fixed permutation). The
seed only shuffles each block's order and draws the tokens, uniform over
the vocabulary. So the work per window does not move with the seed.

Distributions: ``{"dist": "lognormal", "median", "sigma", "min", "max",
"round_up"}``, ``{"dist": "cycle", "values": [...]}`` (the block is the
list itself) and ``{"dist": "fixed", "value": n}``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

PAIRING_SEED = 12345       # fixes which prompt length goes with which output


@dataclass
class Spec:
    """One request as the generator made it."""
    index: int
    prompt: List[int]
    max_new_tokens: int
    due: Optional[float] = None     # open loop: seconds after the start
    meta: dict = field(default_factory=dict)


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def _lengths(dist: dict, n: int) -> np.ndarray:
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]))
    if kind == "cycle":
        vals = np.asarray(dist["values"], int)
        assert len(vals) == n, "a cycle's block is its list of values"
        return vals
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.round(x), dist["min"], dist["max"])
    step = dist.get("round_up")
    if step:
        x = np.ceil(x / step) * step
    return x.astype(int)


def block_sizes(mix: dict) -> List[tuple]:
    """The (prompt, output) lengths every block holds."""
    n = int(mix["block"])
    prompts = _lengths(mix["prompt"], n)
    outputs = _lengths(mix.get("output", {"dist": "fixed", "value": 1}), n)
    outputs = outputs[np.random.default_rng(PAIRING_SEED).permutation(n)]
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def max_context(mix: dict) -> int:
    """The longest prompt plus output any request of the mix can hold."""
    return max(p + o for p, o in block_sizes(mix))


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Spec]:
    """The seed's endless request sequence."""
    rng = np.random.default_rng(seed)
    sizes = block_sizes(mix)
    arrivals = mix["arrivals"]
    rate = arrivals.get("rate") if arrivals["process"] == "poisson" else None
    t, i = 0.0, 0
    while True:
        for k in rng.permutation(len(sizes)):
            p, o = sizes[k]
            due = None
            if rate is not None:
                t += float(rng.exponential(1.0 / rate))
                due = t
            yield Spec(i, rng.integers(0, vocab, p).tolist(), o, due)
            i += 1


def sample(finished: List, n: int, seed: int, size) -> List:
    """``n`` of the finished requests, drawn from the seed, the longest
    (by ``size``) always among them."""
    if len(finished) <= n:
        return list(finished)
    order = sorted(range(len(finished)), key=lambda k: -size(finished[k]))
    rest = np.random.default_rng([seed, 7]).permutation(order[1:])
    pick = sorted([order[0]] + [int(k) for k in rest[:n - 1]])
    return [finished[k] for k in pick]

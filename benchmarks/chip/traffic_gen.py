"""The one traffic generator: reads a mix's parameters and draws requests
or batches from the seed.

Every seed gets the same set of sizes and gaps, in another order: lengths
and inter-arrival gaps are the quantiles of their distributions at evenly
spaced points, one set per block of ``block`` requests, shuffled within the
block by the seed.  So the work of a window does not hinge on the seed; the
seed changes the order, the tokens and the weights."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "exponential":
        v = -dist["mean"] * np.log1p(-u)
    elif kind == "constant":
        v = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist or "max" in dist:
        v = np.clip(v, dist.get("min", -np.inf), dist.get("max", np.inf))
    return v


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def serve_requests(mix: dict, seed: int, count: int, vocab: int) -> list[dict]:
    """``count`` requests: ``due_s`` (offset from the window's start; 0 for
    a backlog), ``tokens`` (the prompt) and ``max_new``."""

    block = mix["block"]
    plen = np.rint(quantiles(mix["prompt_len"], block)).astype(int)
    olen = np.rint(quantiles(mix["output_len"], block)).astype(int)
    rate = mix.get("rate_per_s")
    gaps = quantiles({"kind": "exponential", "mean": 1.0 / rate}, block) if rate else None
    out, due = [], 0.0
    for b in range(math.ceil(count / block)):
        rng = _rng(seed, b)
        p, o = rng.permutation(plen), rng.permutation(olen)
        g = rng.permutation(gaps) if gaps is not None else None
        for i in range(block):
            if len(out) == count:
                break
            if g is not None:
                due += float(g[i])
            out.append({
                "due_s": due,
                "tokens": rng.integers(1, vocab, size=int(p[i]), dtype=np.int32),
                "max_new": int(o[i]),
            })
    return out


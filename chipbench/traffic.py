"""The one traffic generator: a cell's ``traffic`` parameters in, a job of
requests out.

Lengths are a stratified sample of a clipped lognormal: request ``i`` of
``n`` takes the ``(i + 0.5) / n`` quantile. So every seed gets the same
set of prompt lengths and the same set of output lengths, and the seed
only pairs and orders them and draws the prompt tokens: runs with
different seeds do the same amount of work. Prompt lengths are rounded up
to ``round`` tokens (a run then uses a few prefill-chunk widths, each a
program of its own).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np


def lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths: quantiles of lognormal(median, sigma), clipped to
    ``[min, max]`` and rounded up to a multiple of ``round``."""
    nd = NormalDist()
    r = int(spec.get("round", 1))
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, lo), hi)
        x = -(-math.ceil(x) // r) * r
        out.append(min(max(x, lo), hi))
    return out


def job(traffic: Dict[str, Any], seed: int, vocab: int
        ) -> List[Tuple[List[int], int]]:
    """``(prompt tokens, max new tokens)`` per request, in submission
    order."""
    n = int(traffic["requests"])
    p = np.asarray(lengths(traffic["prompt"], n))
    o = np.asarray(lengths(traffic["output"], n))
    rng = np.random.default_rng(seed)
    p = p[rng.permutation(n)]
    o = o[rng.permutation(n)]
    toks = rng.integers(0, vocab, size=int(p.sum()), dtype=np.int64)
    out, at = [], 0
    for lp, lo in zip(p, o):
        out.append((toks[at:at + lp].tolist(), int(lo)))
        at += lp
    return out


def distinct_prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    return sorted(set(lengths(traffic["prompt"], int(traffic["requests"]))))

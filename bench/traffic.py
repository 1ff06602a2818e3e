"""One generator for every serving mix: arrival times and request sizes
from a mix file (``bench/traffic/<cell>.json``) and a seed.

A mix is data. Its keys:

  arrivals  {"kind": "poisson", "rate": req/s}
            The n = floor(rate x seconds) gaps of a window are the
            exponential's n quantiles, in an order drawn from the seed.
  prompt, output
            {"dist": "lognormal", "median", "sigma", "min", "max"}; an
            optional "round_to" rounds a length up to a multiple. The n
            lengths are the distribution's n quantiles, clipped, in an
            order drawn from the seed.

Every seed of a window length thus offers the same gaps and the same sizes,
in another order: the seed changes which request comes when, and the
contents of each, but not the amount of work. The prompt lengths a window
can offer are then known before it starts (``prompt_lengths``), and set-up
compiles each of them.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _count(mix: dict, seconds: float) -> int:
    spec = mix["arrivals"]
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    return int(math.floor(spec["rate"] * seconds))


def _lengths(dist: dict, n: int) -> np.ndarray:
    """The distribution's ``n`` quantiles, clipped and rounded, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    step = dist.get("round_to", 1)
    return -(-x // step) * step


def prompt_lengths(mix: dict, seconds: float) -> List[int]:
    """Every prompt length a window of ``seconds`` offers, whatever the
    seed."""
    return sorted(set(_lengths(mix["prompt"], _count(mix, seconds))
                      .tolist()))


def requests(mix: dict, seconds: float,
             seed: int) -> List[Tuple[float, int, int]]:
    """``(due_s, prompt_len, output_len)`` of every request due in the
    window, in due order."""
    n = _count(mix, seconds)
    rng = np.random.default_rng([seed, 1])
    gaps = -np.log1p(-_quantiles(n)) / mix["arrivals"]["rate"]
    due = np.cumsum(rng.permutation(gaps))
    p = rng.permutation(_lengths(mix["prompt"], n))
    o = rng.permutation(_lengths(mix["output"], n))
    return [(float(t), int(a), int(b))
            for t, a, b in zip(due, p, o) if t < seconds]

"""The one traffic generator: reads a mix's parameters, draws from a seed.

A mix (``bench/traffic/<name>.json``) is data only:

  loop            "closed" (back-to-back calls) or "open" (arrivals on a
                  schedule, whether or not earlier requests finished)
  batch, frames   closed loop: utterances per call and frames per utterance
  rate_per_s      open loop: mean arrival rate (Poisson)
  prompt_frames   open loop: prompt length distribution
  new_frames      open loop: fed-back frames each request asks for
  max_batch       serving engine slots

A length distribution is ``{"dist": "uniform", "lo": a, "hi": b}``
(integers, both ends included), ``{"dist": "fixed", "value": n}`` or
``{"dist": "lognormal", "median": m, "sigma": s, "round_up": k, "lo": a,
"hi": b}`` (rounded up to a multiple of k, then clipped to [a, b]).

Every seed gets the same multiset of sizes and inter-arrival gaps: each is
taken at the evenly spaced quantiles (i + 1/2) / N of its distribution, and
the seed only permutes them and draws the frames' values.  So two seeds
offer the same work in another order, and the spread between seeds is that
of the system, not of the sample.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

LOOPS = ("closed", "open")


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float           # offset from the window's start
    prompt: np.ndarray     # (T, X) float32
    new_frames: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), stream])


def quantile_sizes(dist: dict, n: int) -> List[int]:
    """The n evenly spaced quantiles of a length distribution, ascending."""
    kind = dist["dist"]
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind == "uniform":
        lo, hi = int(dist["lo"]), int(dist["hi"])
        return [lo + min(int(u * (hi - lo + 1)), hi - lo) for u in us]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        k, lo, hi = int(dist["round_up"]), int(dist["lo"]), int(dist["hi"])
        normal = statistics.NormalDist()
        out = []
        for u in us:
            v = math.exp(mu + sigma * normal.inv_cdf(u))
            out.append(min(max(k * math.ceil(v / k), lo), hi))
        return out
    raise ValueError(f"unknown length distribution {kind!r}")


def arrivals(rate_per_s: float, n: int, seed: int) -> List[float]:
    """Due times of n Poisson arrivals: exponential gaps at evenly spaced
    quantiles, in an order drawn from the seed."""
    if rate_per_s <= 0:
        raise ValueError(f"arrival rate {rate_per_s} must be positive")
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate_per_s
                     for i in range(n)])
    return list(np.cumsum(_rng(seed, 1).permutation(gaps)))


def open_loop(mix: dict, seed: int, seconds: float, x_dim: int
              ) -> List[Request]:
    """The requests due in a window of ``seconds``: round(rate x seconds)
    of them, sizes and gaps as the module doc says."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    due = arrivals(mix["rate_per_s"], n, seed)
    prompt = _rng(seed, 2).permutation(quantile_sizes(mix["prompt_frames"],
                                                      n))
    new = _rng(seed, 3).permutation(quantile_sizes(mix["new_frames"], n))
    values = _rng(seed, 4)
    return [Request(i, float(due[i]),
                    values.standard_normal((int(prompt[i]), x_dim),
                                           dtype=np.float32),
                    int(new[i]))
            for i in range(n)]


def check_mix(mix: dict) -> None:
    """Refuse a mix whose parameters the generator cannot read."""
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop {mix.get('loop')!r}; one of {LOOPS}")
    if mix["loop"] == "closed":
        for key in ("batch", "frames"):
            if int(mix[key]) < 1:
                raise ValueError(f"closed-loop {key} must be >= 1")
    else:
        quantile_sizes(mix["prompt_frames"], 1)
        quantile_sizes(mix["new_frames"], 1)
        if float(mix["rate_per_s"]) <= 0 or int(mix["max_batch"]) < 1:
            raise ValueError("open loop needs rate_per_s > 0, max_batch >= 1")

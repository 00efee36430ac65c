"""Open-loop arrival schedules and request lengths, from a traffic mix's
parameters and a seed.

Every seed gets the same multiset of sizes and gaps in another order:
values are drawn at stratified quantiles ``(i + 1/2) / n`` of the stated
distribution and the seed shuffles them.  So two seeds offer the same
work, and differ only in what comes when.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np


def _quantiles(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation((np.arange(n) + 0.5) / n)


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths.  ``spec`` is one of

    * ``{"dist": "lognormal", "median": m, "sigma": s, "clip": [lo, hi],
      "round_up_to": [a, b, ...]}`` (``round_up_to`` optional: each length
      becomes the smallest listed value at or above it, the largest where
      none is);
    * ``{"dist": "choice", "values": [...], "weights": [...]}``."""
    u = _quantiles(n, rng)
    if spec["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        lo, hi = spec.get("clip", (1, math.inf))
        v = np.clip(np.ceil(v), lo, hi)
        if "round_up_to" in spec:
            grid = np.asarray(sorted(spec["round_up_to"]))
            v = grid[np.minimum(np.searchsorted(grid, v), len(grid) - 1)]
        return v.astype(np.int64)
    if spec["dist"] == "choice":
        w = np.asarray(spec.get("weights", [1.0] * len(spec["values"])), float)
        cum = np.cumsum(w / w.sum())
        idx = np.minimum(np.searchsorted(cum, u, side="right"), len(cum) - 1)
        return np.asarray(spec["values"], np.int64)[idx]
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _bursty_warp(s: np.ndarray, rate: float, factor: float, burst_s: float,
                 period_s: float) -> np.ndarray:
    """Map unit-rate arrival times onto a rate that is ``factor`` times the
    base for the first ``burst_s`` seconds of every ``period_s``, with the
    mean rate ``rate``."""
    base = rate * period_s / (period_s - burst_s + factor * burst_s)
    per_period = base * (period_s - burst_s + factor * burst_s)
    k = np.floor(s / per_period)
    r = s - k * per_period
    in_burst = r < base * factor * burst_s
    t = np.where(in_burst, r / (base * factor),
                 burst_s + (r - base * factor * burst_s) / base)
    return k * period_s + t


def arrival_times(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times in seconds from the start of the window.
    ``spec``: ``{"process": "poisson", "rate": r}`` or ``{"process":
    "bursty", "rate": r, "burst_factor": f, "burst_s": b, "period_s": p}``
    (``rate`` is the mean rate in requests per second)."""
    gaps = -np.log1p(-_quantiles(n, rng))      # unit-rate exponential
    s = np.cumsum(gaps)
    if spec["process"] == "poisson":
        return s / spec["rate"]
    if spec["process"] == "bursty":
        return _bursty_warp(s, spec["rate"], spec["burst_factor"],
                            spec["burst_s"], spec["period_s"])
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def schedule(mix: Dict, seconds: float, vocab: int, seed: int
             ) -> List[Tuple[float, np.ndarray, int]]:
    """The requests of one run: ``(arrival_s, prompt_tokens, answer_len)``
    for every arrival inside ``seconds``, in arrival order."""
    rng = np.random.default_rng(seed)
    n = int(math.ceil(mix["arrivals"]["rate"] * seconds * 1.5)) + 16
    times = arrival_times(mix["arrivals"], n, rng)
    prompts = lengths(mix["prompt"], n, rng)
    answers = lengths(mix["answer"], n, rng)
    out = []
    for t, p, a in zip(times, prompts, answers):
        if t >= seconds:
            break
        out.append((float(t), rng.integers(0, vocab, int(p)).astype(np.int32),
                    int(a)))
    return out

"""The one traffic generator: a traffic file's parameters and a seed in,
requests out.

Every seed gets the same work in another order.  Lengths are the quantiles
``(i + 1/2) / n`` of the file's distribution, rounded to a multiple of its
``quantum`` and clipped to its range; open-loop gaps are the
quantiles of an exponential distribution at the file's rate, scaled so that
the ``n = round(rate * seconds)`` arrivals span the window.  The seed
permutes lengths and gaps and draws the prompt tokens, so two seeds differ in
which request is long and when, not in how much there is to do.

Traffic file keys:

- ``arrival``: ``"poisson"`` (open loop at ``rate_per_s``) or ``"backlog"``
  (``backlog`` requests queued before the window opens);
- ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max", "quantum"}`` or ``{"dist": "uniform", "min", "max",
  "quantum"}``; the quantum bounds the number of distinct shapes (prefill
  tails, output lengths) that set-up has to warm;
- ``check_requests``: how many finished requests the correctness check
  compares with the reference (the one with the longest output among them).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    rid: str
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # (P,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles, in ascending order."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.round(x / spec["quantum"]) * spec["quantum"]
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def count(spec: Dict, seconds: float) -> int:
    if spec["arrival"] == "backlog":
        return int(spec["backlog"])
    return max(1, round(spec["rate_per_s"] * seconds))


def generate(spec: Dict, *, seed: int, seconds: float, vocab: int,
             rate_per_s: Optional[float] = None) -> List[Request]:
    """The requests of one run, in order of due time.  ``rate_per_s``
    overrides the file's rate (the knee sweep)."""
    if rate_per_s is not None:
        spec = dict(spec, rate_per_s=rate_per_s)
    n = count(spec, seconds)
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(lengths(spec["prompt"], n))
    outputs = rng.permutation(lengths(spec["output"], n))
    if spec["arrival"] == "poisson":
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / spec["rate_per_s"])
        starts = np.cumsum(gaps) - gaps
        due = starts * (seconds / gaps.sum())
    elif spec["arrival"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival {spec['arrival']!r}")
    return [Request(rid=f"r{i}", due=float(due[i]),
                    prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                        dtype=np.int32),
                    max_new=int(outputs[i]))
            for i in range(n)]


def shapes(spec: Dict, *, seconds: float, chunk: int) -> Dict[str, List[int]]:
    """Every prefill tail length and output length this mix can produce at
    ``seconds`` (the same for every seed), for the warm-up."""
    n = count(spec, seconds)
    p = lengths(spec["prompt"], n)
    o = lengths(spec["output"], n)
    tails = sorted({int(x - chunk * math.floor((x - 1) / chunk)) for x in p})
    return {"tails": tails, "outputs": sorted({int(x) for x in o}),
            "prompt_max": int(p.max()), "output_max": int(o.max())}

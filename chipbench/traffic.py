"""One generator for every traffic mix in ``traffic/*.json``.

A mix is a list of request classes.  Each class has a Poisson arrival
process and a prompt and an output length distribution:

* ``arrival``: ``{"process": "poisson", "rate": R}`` (requests per
  second).  A rate is a number, or ``{"knee": <name>, "multiple": m}``
  for ``m`` times a knee recorded under ``knees`` in the same file.
* ``prompt`` / ``output``: ``{"dist": "loguniform"}`` or
  ``{"dist": "uniform"}`` between ``min`` and ``max``; a prompt is
  rounded up to a multiple of ``round_up`` (the shape grid the
  benchmark warms up).

Every seed gets the same set of requests in another order.  The count,
the inter-arrival gaps and the lengths are the distributions' quantiles
at evenly spaced probabilities; the seed permutes the gaps, pairs the
outputs with the prompts and permutes the arrival order of the pairs.
The pairing is stratified: any ``STRATA`` prompts adjacent in length
carry one output from each ``1/STRATA`` of the output distribution, so
whichever part of the set a window above the knee serves first (the
program admits the shortest prompt waiting) holds the whole spread of
outputs.  The first request is due when the window opens.  The shape of
the traffic (a Poisson body, heavy-tailed lengths) follows
``core/cluster_sim.production_trace``, driven here by the wall clock.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the window opens
    prompt_len: int
    output_len: int
    klass: str


def rate_of(spec, mix: Dict) -> float:
    if isinstance(spec, (int, float)):
        return float(spec)
    knee = mix["knees"][spec["knee"]]["rate_per_s"]
    return float(spec["multiple"]) * float(knee)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, clipped and
    rounded up to its grid (unordered: callers permute them)."""
    u = _quantiles(n)
    lo, hi = dist["min"], dist["max"]
    kind = dist["dist"]
    if kind == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.clip(np.rint(x), lo, hi).astype(np.int64)
    r = dist.get("round_up", 1)
    return np.minimum(-(-x // r) * r, hi - hi % r if r > 1 else hi)


def grid(dist: Dict) -> List[int]:
    """Every length ``lengths`` can return for ``dist``."""
    r = dist.get("round_up", 1)
    lo = -(-dist["min"] // r) * r
    return list(range(lo, dist["max"] + 1, r))


def stratified(n: int, strata: int,
               rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` in which every run of ``strata``
    consecutive places takes one index from each of the ``strata``
    contiguous ranges that split ``range(n)``."""
    groups = [list(rng.permutation(np.flatnonzero(
        np.arange(n) * strata // n == k))) for k in range(strata)]
    out: List[int] = []
    while any(groups):
        out += rng.permutation([g.pop() for g in groups if g]).tolist()
    return np.asarray(out, dtype=np.int64)


STRATA = 4


def class_arrivals(cls: Dict, mix: Dict, seconds: float,
                   rng: np.random.Generator) -> List[Arrival]:
    arr = cls["arrival"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = rate_of(arr["rate"], mix)
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
    # the first request is due as the window opens; the n gaps span it
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * seconds / gaps.sum()
    p = np.sort(lengths(cls["prompt"], n))
    o = np.sort(lengths(cls["output"], n))[stratified(n, STRATA, rng)]
    order = rng.permutation(n)
    return [Arrival(float(d), int(p[i]), int(o[i]), cls["name"])
            for d, i in zip(due, order)]


def schedule(mix: Dict, seconds: float, seed: int) -> List[Arrival]:
    """The window's requests, in due order: the same set for every seed,
    in the seed's order."""
    rng = np.random.default_rng([seed, 0])
    out: List[Arrival] = []
    for cls in mix["classes"]:
        out += class_arrivals(cls, mix, seconds, rng)
    return sorted(out, key=lambda a: (a.due_s, a.klass))


def prompt_ids(arrivals: Sequence[Arrival], vocab: int,
               seed: int) -> List[np.ndarray]:
    """Seeded token ids for each request's prompt."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=a.prompt_len, dtype=np.int64)
            for a in arrivals]


def prompt_grid(mix: Dict) -> List[int]:
    """Every prompt length the mix can send."""
    return sorted({n for cls in mix["classes"] for n in grid(cls["prompt"])})

"""End-to-end metrics of one run, from the harness's own host-clock
stamps: each request's due time on the generator's schedule, and the
time of each of its tokens (stamped after the cluster step that emitted
it).

``percentile`` is the nearest-rank percentile of
``serving/metrics.percentile``.  ``ttfts`` (for the logs and the knee
sweep) counts a request that never produced a first token as infinitely
late.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (NaN on empty input)."""
    if not xs:
        return float("nan")
    xs = sorted(xs)
    k = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
    return xs[k]


def ttfts(reqs: List[Dict]) -> List[float]:
    out = []
    for r in reqs:
        t = r["tokens"][0] if r["tokens"] else math.inf
        out.append(t - r["due"])
    return out


def gaps(reqs: List[Dict], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of a request whose later
    token was stamped inside the window."""
    out = []
    for r in reqs:
        ts = r["tokens"]
        out += [b - a for a, b in zip(ts, ts[1:]) if t0 <= b <= t1]
    return out


def _itl_p95_ms(run):
    return 1e3 * percentile(gaps(run["requests"], run["t0"], run["t1"]), 95)


def _tok_s(run):
    t0, t1 = run["t0"], run["t1"]
    gen = sum(1 for r in run["requests"] for t in r["tokens"]
              if t0 <= t <= t1)
    return (run["prefill_tokens"] + gen) / (t1 - t0)


def _setup_s(run):
    return run["setup_s"]


METRICS = {
    "setup_s": _setup_s,
    "itl_p95_ms": _itl_p95_ms,
    "tok_s": _tok_s,
}


def compute(names: Sequence[str], run: Dict) -> Dict[str, float]:
    return {n: METRICS[n](run) for n in names}

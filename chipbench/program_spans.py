"""The program's own spans (``repro.serving.tracing``), read from its
in-process recorder after the window, and put on the profiler's clock.

A program without that module has nothing to read: every function here
returns ``None`` for it, and so do the readers built on them.
"""
from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

# the harness's span around each ``ClusterEngine.step``: its ends, on
# the profiler's clock, anchor the program's clock to the trace
ANCHOR = "cluster.step"
MIN_ANCHORS = 20
MAX_SPREAD_NS = 200_000
CONTROL_PLANE = ("cluster.route", "cluster.plan", "cluster.finalize")
SYNC = "engine.sync"        # the host waits on the device


def window_spans(t0: float, t1: float) -> Optional[List]:
    """The recorder's spans that overlap ``[t0, t1]`` (``perf_counter``
    seconds); ``None`` where the program has no recorder, or its ring
    dropped spans from that interval."""
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    rec = tracing.RECORDER
    lo, hi = t0 * 1e9, t1 * 1e9
    if not rec.holds_since(lo):
        return None
    return [s for s in rec.spans()
            if s.t1 is not None and s.t1 > lo and s.t0 < hi]


def self_ns(spans) -> dict:
    """Each span's duration less the part its child spans cover, by
    index."""
    own = {s.index: s.t1 - s.t0 for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= s.t1 - s.t0
    return own


def innermost(spans) -> List[Tuple[int, int, str]]:
    """``(start, end, name)`` pieces of time, each in the innermost span
    open over it (spans nest, as the recorder keeps them); time outside
    every span is in no piece."""
    out: List[Tuple[int, int, str]] = []
    stack: list = []
    cur = None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1].t1 <= t:
            top = stack.pop()
            if top.t1 > cur:
                out.append((cur, top.t1, top.name))
            cur = top.t1

    for s in sorted(spans, key=lambda s: s.index):
        close_until(s.t0)
        if stack and s.t0 > cur:
            out.append((cur, s.t0, stack[-1].name))
        cur = s.t0
        stack.append(s)
    close_until(float("inf"))
    return out


def offset_ns(run, trace) -> Optional[float]:
    """Profiler time less ``perf_counter`` time, in ns: the median over
    the traced steps of the end of the harness's ``ANCHOR`` span less
    that step's ``t1``, paired in order.  ``None`` where the pairs do
    not match up, number fewer than ``MIN_ANCHORS``, or spread by more
    than ``MAX_SPREAD_NS``."""
    a, b = run["trace_t"]
    ends = [s["t1"] * 1e9 for s in run["steps"] if a <= s["t1"] <= b]
    marks = [e for _, e, n in sorted(trace.spans) if n == ANCHOR]
    if len(ends) != len(marks) or len(ends) < MIN_ANCHORS:
        return None
    diffs = [m - t for m, t in zip(marks, ends)]
    if max(diffs) - min(diffs) > MAX_SPREAD_NS:
        return None
    return statistics.median(diffs)


def merged(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap_ns(xs, ys) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total

"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle
time, per-operation device time, and idle gaps labelled by host span.

The run opens a host span named ``WINDOW`` around the traced part of
its window, and spans named after what the host is doing (``SPANS``)
inside it.  On each device plane the busy time is the union of the
intervals of the operations on its ``XLA Ops`` line, clipped to the
window; the idle gaps are the holes in that union, each labelled by
the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


@dataclass
class Device:
    name: str
    busy_s: float
    gaps: List[Tuple[float, float]]                  # (start, end) ns
    ops: Dict[str, float] = field(default_factory=dict)   # short name -> s
    # self seconds of each event, by its whole HLO line
    op_events: Dict[str, List[float]] = field(default_factory=dict)


@dataclass
class Trace:
    window_s: float
    devices: List[Device]
    spans: List[Tuple[float, float, str]]            # (start, end, name)

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def op_seconds(self, match) -> Tuple[float, int]:
        """Summed device seconds (averaged over devices) and event
        count (summed) of the operations whose name ``match`` accepts."""
        secs, count = 0.0, 0
        for d in self.devices:
            for name, durs in d.op_events.items():
                if match(name):
                    secs += sum(durs)
                    count += len(durs)
        return secs / len(self.devices), count

    def top_ops(self, n: int = 10) -> List[List]:
        total: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, s in d.ops.items():
                total[name] += s / len(self.devices)
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle seconds (averaged over devices) by the host span that
        covered each gap, largest first."""
        total: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for a, b in d.gaps:
                total[self.label((a + b) / 2)] += (b - a) * 1e-9 / len(
                    self.devices)
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def label(self, t: float) -> str:
        best: Optional[Tuple[float, str]] = None
        for a, b, name in self.spans:
            if a <= t <= b and (best is None or b - a < best[0]):
                best = (b - a, name)
        return best[1] if best else "outside spans"


def op_name(text: str) -> str:
    """The operation's HLO name without its instance number: the trace
    names an op by its whole HLO line (``%fusion.12 = bf16[...] ...``)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


def is_kernel(text: str) -> bool:
    """Whether a trace op is a Pallas (Mosaic) kernel call."""
    return 'custom_call_target="tpu_custom_call"' in text


def _self_times(events: List[Tuple[float, float, str]]
                ) -> List[Tuple[str, float]]:
    """Each event's duration less the time its nested events cover: a
    loop or call op on the ``XLA Ops`` line spans the ops it runs."""
    out: List[List] = []
    stack: List[int] = []
    for a, b, n in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(b, parent[2]) - a
        out.append([n, b - a, b])
        stack.append(len(out) - 1)
    return [(n, t) for n, t, _ in out]


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float
           ) -> Tuple[float, List[Tuple[float, float]]]:
    """Covered nanoseconds of ``intervals`` within [lo, hi], and the
    uncovered gaps."""
    covered, gaps, cur = 0.0, [], lo
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a or b <= cur:
            continue
        if a > cur:
            gaps.append((cur, a))
        covered += b - max(a, cur)
        cur = b
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read(path: str, span_names=None) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans = []
    devices_raw = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif span_names is None or e.name in span_names:
                        spans.append((e.start_ns, e.end_ns, e.name))
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [line for line in plane.lines if line.name == OPS_LINE]
            if ops:
                devices_raw.append((plane.name, [
                    (e.start_ns, e.end_ns, e.name) for e in ops[0].events]))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    if not devices_raw:
        found = {p.name: [ln.name for ln in p.lines] for p in pd.planes}
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane with an "
                         f"{OPS_LINE!r} line; planes {found}")
    lo, hi = window
    devices = []
    for name, events in sorted(devices_raw):
        inside = [(max(a, lo), min(b, hi), n) for a, b, n in events
                  if b > lo and a < hi]
        covered, gaps = _union([(a, b) for a, b, _ in inside], lo, hi)
        per: Dict[str, List[float]] = defaultdict(list)
        for n, t in _self_times(inside):
            per[n].append(t * 1e-9)
        short: Dict[str, float] = defaultdict(float)
        for n, v in per.items():
            short[op_name(n)] += sum(v)
        devices.append(Device(name, covered * 1e-9, gaps, dict(short),
                              dict(per)))
    spans = [(a, b, n) for a, b, n in spans if b > lo and a < hi]
    return Trace((hi - lo) * 1e-9, devices, spans)

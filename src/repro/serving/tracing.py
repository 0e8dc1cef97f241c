"""Spans and counts of the serving step, kept in memory and written onto
the profiler's clock.

``span(name, **attrs)`` times one phase of host work::

    with tracing.span("engine.decode", rids=(3, 7)) as sp:
        ...
        sp.set(kv_live_tokens=n)

Each span is kept in ``RECORDER``'s ring as a ``Span``: its name, start
and end (``time.perf_counter_ns``), the index of the span open around
it (``-1`` at the top) and its attributes, the counts of the work done
inside it.  It also opens a ``jax.profiler.TraceAnnotation`` of the same
name, so a profiled run shows it on the host plane, on the device
events' clock.  ``RECORDER.totals()`` gives operators, per span name,
the count, the seconds and the sum of each numeric attribute since the
recorder was last cleared, dropped spans included.

Every backend compile JAX reports (``COMPILE_EVENT``) is added to the
innermost open span, as attributes ``compiles`` (programs) and
``compile_s`` (seconds): a step that recompiled says so.

The recorder is process-wide and single-threaded: spans open and close
on the serving thread, properly nested.  They belong on host code paths
only; inside a function that ``jax.jit`` traces a span would time the
trace, once.  ``ENABLED = False`` turns recording off (``span`` then
returns a span that records nothing).

The ring holds ``RING_SPANS`` = 65,536 spans.  A cluster step that only
decodes records six (route, plan, ``engine.step`` with its decode and
sync, finalize) and one that runs a prefill chunk four or five more; on
one v5e chip a step of the longdoc cell takes at least 50 ms, so a 51 s
window records at most about 11,000 and its warm-up a few hundred: the
ring holds five such windows.  Spans pushed out of a full ring are counted in
``RECORDER.dropped``, and ``RECORDER.holds_since(t_ns)`` says whether
every span that ended at or after ``t_ns`` is still there.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List

import jax
from jax.profiler import TraceAnnotation

ENABLED = True
RING_SPANS = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_now = time.perf_counter_ns


class Span:
    """One timed phase: ``name``, ``t0`` and ``t1`` in
    ``perf_counter`` nanoseconds, ``parent`` (the ``index`` of the span
    open around it, or -1) and ``attrs``."""

    __slots__ = ("name", "t0", "t1", "parent", "index", "attrs", "_ann")

    def __init__(self, name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = None
        self.parent = self.index = -1

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        rec = RECORDER
        stack = rec.stack
        self.parent = stack[-1].index if stack else -1
        self.index = rec.opened
        rec.opened += 1
        if len(rec.ring) >= rec.size:
            rec.drop_oldest()
        rec.ring.append(self)
        stack.append(self)
        self._ann = ann = TraceAnnotation(self.name)
        ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = t1 = _now()
        self._ann.__exit__(None, None, None)
        self._ann = None
        rec = RECORDER
        rec.stack.pop()
        if self.index < rec.opened - len(rec.ring):
            # pushed out of the ring while it was open
            rec.lost_ns = max(rec.lost_ns, t1)
            _fold(rec.folded, self)


class _Off:
    """What ``span`` returns while recording is off."""

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass


_OFF = _Off()


class Recorder:
    """The ring of spans, the stack of open ones, and the totals."""

    def __init__(self, size: int = RING_SPANS):
        self.size = size
        self.clear()

    def clear(self) -> None:
        self.ring: deque = deque()
        self.stack: List[Span] = []
        self.opened = 0         # spans ever opened: the next span's index
        self.dropped = 0        # spans pushed out of the full ring
        self.lost_ns = -1       # end of the latest span dropped
        self.folded: Dict[str, Dict[str, float]] = {}   # their totals

    def drop_oldest(self) -> None:
        old = self.ring.popleft()
        self.dropped += 1
        if old.t1 is not None:      # else folded when it closes
            self.lost_ns = max(self.lost_ns, old.t1)
            _fold(self.folded, old)

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per span name, over every span closed since the recorder
        was cleared: ``count``, ``seconds`` and the sum of each numeric
        attribute."""
        out = {k: dict(v) for k, v in self.folded.items()}
        for s in self.ring:
            if s.t1 is not None:
                _fold(out, s)
        return out

    def spans(self) -> List[Span]:
        """The spans the ring holds, in the order they opened."""
        return list(self.ring)

    def holds_since(self, t_ns: float) -> bool:
        """Whether no span that ended at or after ``t_ns`` was dropped."""
        return self.lost_ns < t_ns


def _fold(totals: Dict[str, Dict[str, float]], sp: Span) -> None:
    tot = totals.get(sp.name)
    if tot is None:
        tot = totals[sp.name] = {"count": 0, "seconds": 0.0}
    tot["count"] += 1
    tot["seconds"] += (sp.t1 - sp.t0) * 1e-9
    for k, v in sp.attrs.items():
        if type(v) is int or type(v) is float:
            tot[k] = tot.get(k, 0) + v


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT and RECORDER.stack:
        a = RECORDER.stack[-1].attrs
        a["compiles"] = a.get("compiles", 0) + 1
        a["compile_s"] = a.get("compile_s", 0.0) + duration


RECORDER = Recorder()
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def span(name: str, **attrs):
    """A context manager that records one span (see the module's
    docstring); it yields the ``Span``, whose ``set`` adds counts."""
    if not ENABLED:
        return _OFF
    return Span(name, attrs)


def rolled_up(spans: List[Span], name: str, attr: str) -> List[tuple]:
    """``(span, total)`` for each span called ``name`` among ``spans``:
    ``attr`` summed over it and the spans inside it (compiles land on
    the innermost span, a chunk's on its ``engine.chunk.run``)."""
    by_index = {s.index: s for s in spans}
    total = {s.index: 0 for s in spans if s.name == name}
    for s in spans:
        v = s.attrs.get(attr)
        while v and s is not None:
            if s.index in total:
                total[s.index] += v
            s = by_index.get(s.parent)
    return [(by_index[i], total[i]) for i in sorted(total)]

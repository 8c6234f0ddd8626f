"""Host spans of the pool's chunk loop, and what they add up to.

``span(name, **args)`` opens ``jax.profiler.TraceAnnotation("trinity." +
name, **args)``: while a profiler session records, the span lands in the
same trace, on the same clock, as the device's operations; otherwise no
annotation is made at all. Whether a session records or not, the tracer
keeps in memory, for every span name:

- ``stats``: count, total and longest seconds (``time.perf_counter``);
- ``recorded``: the same over the spans opened while a session recorded,
  that is over the spans the trace holds;
- ``call``: self seconds within the current ``run_until`` call (reset as a
  ``run_until`` span opens). A span's self time is the part of it not
  inside a span nested in it, so a leaf span's self time is its duration.

Span arguments carry counts; request ids (``rids``) only while a session
records. One tracer serves the process, as the profiler does.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, Iterable, List

from jax.profiler import TraceAnnotation

PREFIX = "trinity."
CALL = "run_until"  # the span that resets the per-call record

recording = TraceAnnotation.is_enabled


class Stat:
    __slots__ = ("count", "total_s", "max_s")

    def __init__(self):
        self.count, self.total_s, self.max_s = 0, 0.0, 0.0

    def add(self, seconds: float):
        self.count += 1
        self.total_s += seconds
        if seconds > self.max_s:
            self.max_s = seconds


class _Span:
    __slots__ = ("tracer", "name", "args", "ann", "t0", "inner")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer, self.name, self.args = tracer, name, args

    def __enter__(self):
        tr = self.tracer
        if self.name == CALL:
            tr.call.clear()
        self.ann = None
        if recording():
            self.ann = TraceAnnotation(PREFIX + self.name, **self.args)
            self.ann.__enter__()
        tr.stack.append(self)
        self.inner = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        tr = self.tracer
        tr.stack.pop()
        if tr.stack:
            tr.stack[-1].inner += dt
        tr.call[self.name] += dt - self.inner
        tr.stats[self.name].add(dt)
        if self.ann is not None:
            self.ann.__exit__(*exc)
            tr.recorded[self.name].add(dt)
        return False


class Tracer:
    def __init__(self):
        self.stats: Dict[str, Stat] = defaultdict(Stat)
        self.recorded: Dict[str, Stat] = defaultdict(Stat)
        self.call: Dict[str, float] = defaultdict(float)
        self.stack: List[_Span] = []

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)


def rids(ids: Iterable[int]) -> dict:
    """Span arguments naming requests, ``{"rids": "3 7 9"}``, while a
    session records (so the spans of one request share its rid), else
    none."""
    return {"rids": " ".join(map(str, ids))} if recording() else {}


TRACER = Tracer()
span = TRACER.span

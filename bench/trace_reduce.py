"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- The traced window is the extent of the ``bench.window`` host span.
- Device busy time is the union of the intervals in which an operation
  ran on the device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane),
  clipped to the window, averaged over the devices.
- Program time is summed by module name (line ``XLA Modules``) with the
  numeric suffix the compiler adds dropped, so ``jit_extend_multi(12)``
  and ``jit_extend_multi(14)`` are one program. Operation time is summed
  by the HLO instruction's name without its numeric suffix (the Pallas
  kernel is the custom call named after it, ``distance_tasks``); control
  flow (``while`` and the like) is left out, its body's operations count.
  The breakdown lists operations by the instruction's own name and result
  type instead, which says more and holds for one compile only.
- Idle gaps (the window less the busy union of device 0) are attributed to
  the ``bench.<what>`` host spans that overlap them; time in no such span
  is ``other``.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over devices
    devices: int
    program_s: Dict[str, float]  # by module name, summed over devices
    op_s: Dict[str, float]  # by operation name, summed over devices
    op_detail_s: Dict[str, float]  # by instruction and result type
    idle_by_span: Dict[str, float]  # idle seconds of device 0 by host span
    module_calls: Dict[str, int]

    def program(self, prefix: str) -> float:
        """Seconds of all programs whose name starts with ``prefix``."""
        return sum(v for k, v in self.program_s.items()
                   if k.startswith(prefix))

    def ops(self, pattern: str) -> float:
        """Seconds of all operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_s.items() if rx.search(k))

    def breakdown(self, top: int = 10) -> Dict[str, List[list]]:
        ops = sorted(self.op_detail_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def load(path: str) -> List[Plane]:
    """The planes of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for pl in data.planes:
        lines = {}
        for ln in pl.lines:
            lines.setdefault(ln.name, []).extend(
                Event(e.name, float(e.start_ns), float(e.end_ns))
                for e in ln.events)
        out.append(Plane(pl.name, lines))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def complement(busy: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The parts of [lo, hi) not covered by the merged ``busy``."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [g for g in gaps if g[1] > g[0]]


def attribute(gaps: Sequence[Interval], spans: Sequence[Event]
              ) -> Dict[str, float]:
    """Seconds of ``gaps`` covered by each host span name (spans assumed
    not to overlap one another); the rest under ``other``."""
    out: Dict[str, float] = defaultdict(float)
    spans = sorted(spans, key=lambda e: e.start_ns)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(spans) and spans[j].end_ns <= gs:
            j += 1
        k = j
        while k < len(spans) and spans[k].start_ns < ge:
            ov = min(ge, spans[k].end_ns) - max(gs, spans[k].start_ns)
            if ov > 0:
                out[spans[k].name[len(SPAN_PREFIX):]] += ov / 1e9
                covered += ov
            k += 1
        if ge - gs - covered > 0:
            out["other"] += (ge - gs - covered) / 1e9
    return dict(out)


_SUFFIX = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%?([\w.-]+?)(?:\.\d+)? = .*? ([\w-]+)\(")
CONTROL = ("while", "conditional", "call")


def program_name(module: str) -> str:
    return _SUFFIX.sub("", module)


_DETAIL = re.compile(r"^%?([\w.-]+) = (\S+)")
_LAYOUT = re.compile(r"\{[^}]*\}")


def op_name(event: str):
    """(short name, opcode) of an ``XLA Ops`` event, whose name is the HLO
    instruction's text: ``%distance_tasks.6 = f32[1,2048]... custom-call(``
    gives ``("distance_tasks", "custom-call")``."""
    m = _HLO.match(event)
    return (m.group(1), m.group(2)) if m else (event, "")


def op_detail(event: str) -> str:
    """The instruction's own name and its result type without layouts:
    ``distance_tasks.6 f32[1,2048]``. Names differ from one compile to the
    next, so this serves the breakdown only, never a metric."""
    m = _DETAIL.match(event)
    if not m:
        return event[:80]
    return f"{m.group(1)} {_LAYOUT.sub('', m.group(2)).rstrip(',')}"[:80]


def reduce(planes: List[Plane]) -> Reduced:
    host_spans = [e for p in planes if not DEVICE_PLANE.match(p.name)
                  for evs in p.lines.values() for e in evs
                  if e.name.startswith(SPAN_PREFIX)]
    windows = [e for e in host_spans if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in host_spans if e.name != WINDOW_SPAN]
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0.0
    program_s: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    op_detail_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = {}
    for n, dev in enumerate(devices):
        ops = []
        for e in dev.lines.get(OPS_LINE, []):
            c = clip((e.start_ns, e.end_ns), lo, hi)
            if c:
                ops.append(c)
                name, opcode = op_name(e.name)
                if opcode not in CONTROL:  # a loop's time is its body's
                    op_s[name] += (c[1] - c[0]) / 1e9
                    op_detail_s[op_detail(e.name)] += (c[1] - c[0]) / 1e9
        for e in dev.lines.get(MODULES_LINE, []):
            c = clip((e.start_ns, e.end_ns), lo, hi)
            if c:
                program_s[program_name(e.name)] += (c[1] - c[0]) / 1e9
                calls[program_name(e.name)] += 1
        busy = union(ops)
        busy_total += length(busy) / 1e9
        if n == 0:
            idle = attribute(complement(busy, lo, hi), spans)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_total / len(devices),
                   devices=len(devices), program_s=dict(program_s),
                   op_s=dict(op_s), op_detail_s=dict(op_detail_s),
                   idle_by_span=idle,
                   module_calls=dict(calls))

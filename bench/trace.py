"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: the device's busy time and idle gaps, time per jitted program and
per kernel, and the benchmark's own host spans on the same clock.

A trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
Device planes are those named ``/device:<platform>:<n>``; on each, the
``XLA Modules`` line holds one event per program execution and the
``XLA Ops`` line one per operation, named by its HLO text (a Pallas
kernel is one ``custom-call`` operation, named after the jitted wrapper
that calls it, e.g. ``%quant_matmul.46``).  Host spans are the events
whose name starts with ``SPAN_PREFIX``: the benchmark opens them with
``jax.profiler.TraceAnnotation`` around its calls into the program.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]          # (start_s, end_s)


@dataclass
class Event:
    name: str
    start_s: float
    dur_s: float

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


@dataclass
class Trace:
    """Events of one trace, on one clock, in seconds."""
    modules: List[List[Event]] = field(default_factory=list)  # per device
    ops: List[List[Event]] = field(default_factory=list)      # per device
    spans: List[Event] = field(default_factory=list)          # host

    @property
    def n_devices(self) -> int:
        return len(self.ops)


def find(directory: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``directory``, if any."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines and MODULES_LINE not in lines:
                continue
            for name, dest in ((MODULES_LINE, tr.modules), (OPS_LINE, tr.ops)):
                line = lines.get(name)
                dest.append([] if line is None else [
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append(Event(e.name[len(SPAN_PREFIX):],
                                              e.start_ns * 1e-9,
                                              e.duration_ns * 1e-9))
    tr.spans.sort(key=lambda e: e.start_s)
    return tr


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy_s(events: List[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran."""
    cover = clip(union([(e.start_s, e.end_s) for e in events]), lo, hi)
    return sum(b - a for a, b in cover)


def idle_gaps(events: List[Event], lo: float, hi: float
              ) -> List[Interval]:
    """The stretches of [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for a, b in clip(union([(e.start_s, e.end_s) for e in events]), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def window(tr: Trace, span: str) -> Optional[Interval]:
    """[start, end] of the first host span named ``span``."""
    for e in tr.spans:
        if e.name == span:
            return e.start_s, e.end_s
    return None


def within(events: List[Event], lo: float, hi: float) -> List[Event]:
    """Events that start inside [lo, hi]."""
    return [e for e in events if lo <= e.start_s <= hi]


def matching(events: List[Event], pattern: str) -> List[Event]:
    """The events whose name matches the regular expression ``pattern``
    anywhere."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def totals(events: List[Event], pattern: str) -> Tuple[int, float]:
    """(count, total seconds) of the events whose name matches the
    regular expression ``pattern`` anywhere."""
    hits = [e.dur_s for e in matching(events, pattern)]
    return len(hits), sum(hits)


def by_name(events: List[Event]) -> Dict[str, float]:
    """Total seconds per event name, largest first."""
    out: Dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur_s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def _split(op: str) -> Tuple[str, str, str]:
    """``%name = TYPE opcode(...)`` -> (name, TYPE, opcode)."""
    name, _, rest = op.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    if m is None:
        return name.lstrip("%"), "", ""
    return name.lstrip("%"), rest[:max(0, m.start() - 1)], m.group(1)


def short_name(op: str) -> str:
    """``%fusion.3 = f32[8,64]{...} fusion(...)`` -> ``fusion.3
    f32[8,64]``: the operation's name and the type of its result."""
    name, typ, _ = _split(op)
    typ = "tuple" if typ.startswith("(") else typ.split("{", 1)[0]
    return f"{name} {typ}".strip()


def is_container(op: str) -> bool:
    """Control-flow operations, whose events enclose those of the
    operations they run."""
    return _split(op)[2] in CONTAINERS


def host_doing(tr: Trace, t: float, skip: Tuple[str, ...] = ()) -> str:
    """Name of the innermost benchmark span open at time ``t``, or
    ``"outside"`` where none is."""
    best = None
    for e in tr.spans:
        if e.start_s > t:
            break
        if e.end_s >= t and e.name not in skip and \
                (best is None or e.start_s >= best.start_s):
            best = e
    return best.name if best is not None else "outside"

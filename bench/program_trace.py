#!/usr/bin/env python3
"""The serving program's own spans in a traced run: the events whose
name starts with ``PREFIX``, which the program opens around its host
work (``repro.core.metrics.span``), with their integer arguments, on the
clock of the device's operations and of the benchmark's spans
(``bench/trace.py``).

The per-layer metrics of the program's layers read them through
``window_spans``, only from a trace that holds the device's plane: a
run of a program that opens no such span, or a run with no device,
gives them nothing to read.  Each ``repro:admit`` span carries the
boundary's admission outcomes as arguments (``admitted`` and one count
per gate that refused a request), so the admission counters of the
window are read from the trace too.

    python3 bench/program_trace.py [trace_dir]

prints, as one JSON object, what the traced window of the newest trace
under ``trace_dir`` (default ``.bench_trace``, where ``bench/run.py
--trace 1`` leaves it) shows: the ten longest device idle gaps named by
the innermost span open at their middle, of either set, the device's
idle seconds by the innermost span at the time, the admission outcomes,
each program span's count, mean and longest, and whether every paged
decode segment the program launched started on the device after its
launch began.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as trace_mod  # noqa: E402

PREFIX = "repro:"
ADMITTED = "admitted"
OUTSIDE = "outside"
WINDOW = "window"              # the harness's span around the window
SEGMENT_PROGRAM = r"_decode_chunk_paged_fn"


@dataclass
class Span:
    name: str                  # with its ``PREFIX``
    start_s: float
    dur_s: float
    args: Dict[str, int] = field(default_factory=dict)

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


def load(path: str) -> List[Span]:
    """The program's spans of the trace at ``path``, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9,
                                    {k: int(v) for k, v in e.stats}))
    out.sort(key=lambda s: s.start_s)
    return out


@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime_ns: int) -> Tuple[Span, ...]:
    return tuple(load(path))


def of_run(run) -> Optional[List[Span]]:
    """The program's spans of a traced run's trace, or None where the
    run has no trace holding the device's plane."""
    if run.trace is None or run.trace_window is None or not run.trace.ops:
        return None
    from bench.harness import TRACE_DIR
    path = trace_mod.find(str(run.cell.root / TRACE_DIR))
    if path is None:
        return None
    return list(_load_once(path, os.stat(path).st_mtime_ns))


def window_spans(run, name: str) -> Optional[List[Span]]:
    """The program's spans called ``PREFIX + name`` that start inside
    the traced window; None where the run gives nothing to read."""
    spans = of_run(run)
    if spans is None:
        return None
    lo, hi = run.trace_window
    return [s for s in spans
            if s.name == PREFIX + name and lo <= s.start_s <= hi]


def outcomes(admits: List[Span]) -> Dict[str, int]:
    """Admission outcomes summed over ``repro:admit`` spans."""
    out: Dict[str, int] = {}
    for s in admits:
        for k, v in s.args.items():
            out[k] = out.get(k, 0) + v
    return out


def refusals(counts: Dict[str, int]) -> int:
    return sum(v for k, v in counts.items() if k != ADMITTED)


# -- the host's innermost span, over both sets ------------------------------

Labelled = Tuple[float, float, str]


def _named(bench: List[trace_mod.Event], program: List[Span]
           ) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the benchmark's spans but its window, named
    bare as ``bench/trace.py`` names them, and of the program's, with
    their prefix."""
    return [(e.name, e.start_s, e.end_s) for e in bench
            if e.name != WINDOW] + \
        [(s.name, s.start_s, s.end_s) for s in program]


def innermost(bench: List[trace_mod.Event], program: List[Span],
              lo: float, hi: float) -> List[Labelled]:
    """[lo, hi] cut into stretches, each named by the innermost span open
    in it (``OUTSIDE`` where none is).  The spans of one thread nest, so
    the innermost is the open one that started last."""
    spans = sorted((x for x in _named(bench, program)
                    if x[2] > lo and x[1] < hi),
                   key=lambda x: (x[1], -x[2]))
    out: List[Labelled] = []
    stack: List[Tuple[str, float, float]] = []
    t = lo

    def cut(until: float, name: str) -> None:
        nonlocal t
        if until > t:
            out.append((t, until, name))
            t = until

    for x in spans:
        a = max(x[1], lo)
        while stack and stack[-1][2] <= a:
            cut(stack[-1][2], stack.pop()[0])
        cut(a, stack[-1][0] if stack else OUTSIDE)
        stack.append(x)
    while stack:
        top = stack.pop()
        cut(min(top[2], hi), top[0])
    cut(hi, OUTSIDE)
    return out


def idle_by_span(ops: List[trace_mod.Event], bench: List[trace_mod.Event],
                 program: List[Span], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds of [lo, hi] in which no operation ran on the device, by
    the host's innermost span at the time."""
    gaps = trace_mod.idle_gaps(ops, lo, hi)
    out: Dict[str, float] = {}
    i = 0
    for a, b, name in innermost(bench, program, lo, hi):
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            out[name] = out.get(name, 0.0) \
                + min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    return out


def host_bound_idle_s(ops: List[trace_mod.Event],
                      bench: List[trace_mod.Event], program: List[Span],
                      lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which no operation ran on the device while
    the host's innermost span was one of the program's."""
    return sum(v for k, v in idle_by_span(ops, bench, program, lo, hi
                                          ).items() if k.startswith(PREFIX))


def longest_gaps(ops: List[trace_mod.Event], bench: List[trace_mod.Event],
                 program: List[Span], lo: float, hi: float, n: int = 10
                 ) -> List[list]:
    """The ``n`` longest idle gaps of [lo, hi], each as [the innermost
    span open at its middle, seconds]."""
    parts = innermost(bench, program, lo, hi)
    starts = [p[0] for p in parts]
    out = []
    for a, b in sorted(trace_mod.idle_gaps(ops, lo, hi),
                       key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        k = max(0, bisect.bisect_right(starts, mid) - 1)
        out.append([parts[k][2] if parts else OUTSIDE, b - a])
    return out


def launch_precedes_module(launches: List[Span],
                           modules: List[trace_mod.Event]) -> dict:
    """Pairs each execution of the paged decode program with the latest
    ``repro:segment.launch`` that started before it.  On one clock,
    every execution has such a launch and no two share one."""
    starts = [s.start_s for s in launches]
    owner = [bisect.bisect_right(starts, m.start_s) - 1 for m in modules]
    lags = [m.start_s - launches[k].start_s
            for m, k in zip(modules, owner) if k >= 0]
    return {"launches": len(launches), "executions": len(modules),
            "without_launch": sum(k < 0 for k in owner),
            "sharing_a_launch": len(owner) - len(set(owner)),
            "lag_s_min": min(lags) if lags else None,
            "lag_s_max": max(lags) if lags else None}


def report(trace_dir: str) -> dict:
    """What the newest trace under ``trace_dir`` shows of its window (see
    the module's docstring)."""
    path = trace_mod.find(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    tr = trace_mod.load(path)
    program = load(path)
    win = trace_mod.window(tr, WINDOW)
    if win is None or not tr.ops:
        raise ValueError(f"{path}: no window span or no device plane")
    lo, hi = win
    ops = trace_mod.within(tr.ops[0], lo, hi)
    inside = [s for s in program if lo <= s.start_s <= hi]
    stats: Dict[str, list] = {}
    for s in inside:
        st = stats.setdefault(s.name, [0, 0.0, 0.0])
        st[0] += 1
        st[1] += s.dur_s
        st[2] = max(st[2], s.dur_s)
    modules = trace_mod.matching(tr.modules[0], SEGMENT_PROGRAM) \
        if tr.modules else []
    launches = [s for s in program if s.name == PREFIX + "segment.launch"]
    return {
        "trace": path, "window_s": hi - lo,
        "device_idle_s": sum(b - a for a, b in
                             trace_mod.idle_gaps(ops, lo, hi)),
        "idle_s_by_span": idle_by_span(ops, tr.spans, program, lo, hi),
        "idle_gaps": longest_gaps(ops, tr.spans, program, lo, hi),
        "admit_outcomes": outcomes([s for s in inside
                                    if s.name == PREFIX + "admit"]),
        "spans": {k: {"count": c, "mean_ms": 1e3 * t / c,
                      "max_ms": 1e3 * most, "total_s": t}
                  for k, (c, t, most) in sorted(stats.items())},
        "segment_launch": launch_precedes_module(launches, modules)}


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1] if len(sys.argv) > 1
                            else ".bench_trace")))

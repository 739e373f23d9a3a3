"""The program's own spans, read from the profiler's host plane.

While a profiler session runs, every ``obs.span`` of the program lands in
plane ``/host:CPU`` as ``dml:<name>``, one line per thread, with its
scalar attrs as stats, on the clock of the device events.  ``load`` keeps
those, line by line; the rest works on plain tuples, so the tests drive
it with a synthetic trace.  A program without the bridge (the parent of
the PR that added it) writes no such event: every reader then finds
nothing and returns None.

Idle time goes to a span by the thread that drives the device: the line
that launches the programs and, only while that thread has no span open
at all (before a trial's thread starts and after it ends), the line that
started it.  A thread that works beside the device (the checkpoint
writer) is timed by ``mean_seconds`` and takes no idle time.
"""

from __future__ import annotations

import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from benchmark import trace as trace_lib

PREFIX = "dml:"
Interval = Tuple[float, float]


class Span(NamedTuple):
    name: str  # without the prefix
    start: float
    dur: float
    attrs: dict


Lines = List[List[Span]]


def load(path: str, window: Interval) -> Lines:
    """The ``dml:`` events of every host line, clipped to ``window``;
    lines without one are left out."""
    from jax.profiler import ProfileData

    lo, hi = window
    lines: Lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_lib.HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if not ev.name.startswith(PREFIX):
                    continue
                start = ev.start_ns * 1e-9
                a, b = max(start, lo), min(start + ev.duration_ns * 1e-9, hi)
                if b > a:
                    spans.append(Span(ev.name[len(PREFIX):], a, b - a,
                                      dict(ev.stats)))
            if spans:
                lines.append(spans)
    return lines


def of_run(run) -> Optional[Lines]:
    """The traced run's span lines, parsed once and kept on ``run.facts``."""
    if run.trace is None:
        return None
    if "program_spans" not in run.facts:
        path = trace_lib.find_xplane(os.path.join(run.work_dir, "trace"))
        run.facts["program_spans"] = (
            load(path, run.trace_window) if path else []
        )
    return run.facts["program_spans"] or None


def named(lines: Lines, name: str) -> List[Span]:
    return [s for line in lines for s in line if s.name == name]


def lines_holding(lines: Lines, name: str) -> Lines:
    return [line for line in lines if any(s.name == name for s in line)]


def mean_seconds(spans: List[Span], per: Optional[str] = None
                 ) -> Optional[float]:
    """Mean duration of ``spans``; with ``per``, their summed duration over
    their summed attr ``per`` (a cost per item)."""
    if not spans:
        return None
    total = sum(s.dur for s in spans)
    if per is None:
        return total / len(spans)
    items = sum(float(s.attrs.get(per, 0)) for s in spans)
    return total / items if items else None


def idlest_gaps(trace, window: Interval) -> List[Interval]:
    """The idle gaps of the device that is idle longest."""
    best, best_idle = [], -1.0
    for dev in trace.devices.values():
        gaps = trace_lib.idle_gaps(dev, window)
        idle = sum(b - a for a, b in gaps)
        if idle > best_idle:
            best, best_idle = gaps, idle
    return best


def overlap_seconds(gaps: List[Interval], spans: Iterable[Span]) -> float:
    """Seconds of ``gaps`` that fall inside any of ``spans``."""
    cover = trace_lib.union((s.start, s.start + s.dur) for s in spans)
    total, i = 0.0, 0
    for lo, hi in gaps:
        while i < len(cover) and cover[i][1] <= lo:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < hi:
            total += min(hi, cover[j][1]) - max(lo, cover[j][0])
            j += 1
    return total


def innermost(spans: Iterable[Span]) -> List[Tuple[float, float, str]]:
    """One thread's spans as pieces that do not overlap: every instant goes
    to the innermost span open there.  Sorted by start."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []  # name, end
    at = float("-inf")

    def advance(to: float):
        nonlocal at
        while stack and stack[-1][1] <= to:
            name, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end
        if stack and to > at:
            out.append((at, to, stack[-1][0]))
        at = max(at, to)

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        advance(s.start)
        stack.append((s.name, s.start + s.dur))
    advance(float("inf"))
    return out


def attribute(gaps: List[Interval], spans: Iterable[Span]
              ) -> Tuple[Dict[str, float], List[Interval]]:
    """Seconds of ``gaps`` by the innermost span of one thread, and the
    parts of the gaps during which that thread had no span open."""
    pieces = innermost(spans)
    totals: Dict[str, float] = {}
    left: List[Interval] = []
    i = 0
    for lo, hi in gaps:
        while i < len(pieces) and pieces[i][1] <= lo:
            i += 1
        at, j = lo, i
        while j < len(pieces) and pieces[j][0] < hi:
            a, b, name = pieces[j]
            a, b = max(a, lo), min(b, hi)
            if a > at:
                left.append((at, a))
            totals[name] = totals.get(name, 0.0) + (b - a)
            at = b
            j += 1
        if hi > at:
            left.append((at, hi))
    return totals, left


def idle_by_span(gaps: List[Interval], lines: Lines, launch: str,
                 starter: Optional[str] = None) -> Dict[str, float]:
    """Idle seconds by program span: each part of a gap goes to the
    innermost span of the thread that launches the device programs (the
    lines that hold a span ``launch``); what is left while that thread
    has no span open goes to the thread that holds a span ``starter``;
    the rest to ``""``."""
    totals: Dict[str, float] = {}
    left = gaps
    ordered = lines_holding(lines, launch)
    if starter is not None:
        ordered = ordered + [
            line for line in lines_holding(lines, starter)
            if not any(s.name == launch for s in line)
        ]
    for line in ordered:
        got, left = attribute(left, line)
        for name, secs in got.items():
            totals[name] = totals.get(name, 0.0) + secs
    totals[""] = sum(b - a for a, b in left)
    return totals

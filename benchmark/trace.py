"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction is the benchmark's own, so that every PR computes the same
number in the same way: busy and idle time as the union of the intervals
in which an operation ran on a device, a kernel's device time as the sum
of its events, the gaps between programs of a given name, and the longest
idle gaps named by what the benchmark's own host annotations say the host
was doing.

``load`` reads a trace with nothing but JAX into plain tuples; every other
function works on those, so the tests drive them with a synthetic trace.
Times are seconds from the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

# Parts of an operation's HLO text: a layout, the opcode, an instance number.
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_INSTANCE = re.compile(r"\.\d+$")

# What the TPU's planes and lines are called in a trace of this JAX
# (looked at by hand on a real trace, PR 26).
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
# The benchmark's own host annotations start with this.
ANNOTATION_PREFIX = "bench:"


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[str, DeviceTrace] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return paths[-1] if paths else None


def load(path: str, device_prefix: str = DEVICE_PLANE_PREFIX) -> Trace:
    """Read an ``.xplane.pb``: device planes' op and module lines, and the
    host's annotation events that the benchmark wrote itself."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            dev = trace.devices.setdefault(plane.name, DeviceTrace())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_events(line))
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                trace.host.extend(
                    e for e in _events(line)
                    if e[0].startswith(ANNOTATION_PREFIX)
                )
    return trace


def _events(line) -> List[Event]:
    return [
        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
        for ev in line.events
    ]


def describe(path: str, top: int = 25) -> str:
    """Every plane and line of a trace with its busiest event names: what
    one looks at by hand before trusting the names above."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            totals: Dict[str, List[float]] = {}
            n = 0
            for ev in line.events:
                n += 1
                slot = totals.setdefault(ev.name, [0, 0.0])
                slot[0] += 1
                slot[1] += ev.duration_ns * 1e-9
            out.append(f"  line {line.name!r}: {n} events")
            ranked = sorted(totals.items(), key=lambda kv: -kv[1][1])[:top]
            for name, (count, secs) in ranked:
                out.append(f"    {secs:10.6f}s x{count:<6d} {name[:140]}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Reductions


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(events: Iterable[Event], window: Tuple[float, float]) -> List[Event]:
    """The parts of ``events`` that lie inside ``window``."""
    lo, hi = window
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_intervals(dev: DeviceTrace, window) -> List[Tuple[float, float]]:
    return union((s, s + d) for _, s, d in clip(dev.ops, window))


def busy_seconds(dev: DeviceTrace, window) -> float:
    return sum(b - a for a, b in busy_intervals(dev, window))


def trace_window(trace: Trace) -> Tuple[float, float]:
    """From the first to the last device event of any device."""
    starts, ends = [], []
    for dev in trace.devices.values():
        for _, s, d in dev.ops or dev.modules:
            starts.append(s)
            ends.append(s + d)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def annotation_window(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """The span of the host annotation ``name`` (the benchmark's window)."""
    for n, s, d in trace.host:
        if n == name:
            return s, s + d
    return None


def self_times(ops: List[Event]) -> Dict[str, float]:
    """Device seconds by operation name, a container (a loop, a call)
    counted only for what its children leave."""
    totals: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def kernel_seconds(dev: DeviceTrace, window, *, contains: List[str],
                   yields: List[str] = (), yields_no: List[str] = ()
                   ) -> Tuple[float, int]:
    """Summed device time and count of a kernel's events: those whose name
    (on the TPU, the operation's HLO text) holds every string of
    ``contains`` and whose part before the opcode, what the operation
    yields, holds every string of ``yields`` and none of ``yields_no``."""
    total, count = 0.0, 0
    for name, _, dur in clip(dev.ops, window):
        if not all(c in name for c in contains):
            continue
        opcode = _OPCODE.search(name)
        head = name[: opcode.start()] if opcode else name
        if all(y in head for y in yields) and not any(y in head for y in yields_no):
            total += dur
            count += 1
    return total, count


def module_gaps(dev: DeviceTrace, window, match: str) -> List[float]:
    """For successive programs whose name holds ``match``: the time from
    the end of one to the start of the next in which the device ran
    nothing (other programs' device time between them taken out)."""
    mods = sorted(
        (e for e in clip(dev.modules, window) if match in e[0]),
        key=lambda e: e[1],
    )
    busy = busy_intervals(dev, window)
    gaps = []
    for (_, s0, d0), (_, s1, _) in zip(mods, mods[1:]):
        lo, hi = s0 + d0, s1
        if hi <= lo:
            gaps.append(0.0)
            continue
        covered = sum(
            max(0.0, min(b, hi) - max(a, lo)) for a, b in busy
        )
        gaps.append(hi - lo - covered)
    return gaps


def idle_gaps(dev: DeviceTrace, window) -> List[Tuple[float, float]]:
    lo, hi = window
    gaps, at = [], lo
    for a, b in busy_intervals(dev, window):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def name_gaps(gaps: List[Tuple[float, float]], host: List[Event],
              top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap's time goes to
    the innermost benchmark annotation that covers each part of it, the
    rest to ``unannotated``."""
    totals: Dict[str, float] = {}
    # Innermost first: shorter annotations win where several overlap.
    spans = sorted(host, key=lambda e: e[2])
    for lo, hi in gaps:
        left = [(lo, hi)]
        for name, s, d in spans:
            if not left:
                break
            nxt = []
            for a, b in left:
                oa, ob = max(a, s), min(b, s + d)
                if ob > oa:
                    totals[name] = totals.get(name, 0.0) + (ob - oa)
                    if oa > a:
                        nxt.append((a, oa))
                    if b > ob:
                        nxt.append((ob, b))
                else:
                    nxt.append((a, b))
            left = nxt
        for a, b in left:
            totals["unannotated"] = totals.get("unannotated", 0.0) + (b - a)
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]




def short_name(name: str, limit: int = 120) -> str:
    """A device operation's name, short enough to print.  The TPU's trace
    names an operation by its whole HLO text; this keeps the instruction's
    name without its instance number, its opcode and what it yields without
    the layouts: ``%attention custom-call -> (bf16[128,2048,64],
    f32[128,1,2048])``.  Instances of one kind then add up."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    opcode = _OPCODE.search(" " + rest)
    yields = rest[: opcode.start()] if opcode else ""
    yields = _LAYOUT.sub("", yields).strip()
    out = f"{_INSTANCE.sub('', head)} {opcode.group(1) if opcode else ''} -> {yields}"
    return out[:limit]


def breakdown(trace: Trace, window, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time (self time, summed over devices) and the idle time of the
    fullest-idle device by host annotation."""
    ops: Dict[str, float] = {}
    worst_gaps: List[Tuple[float, float]] = []
    worst_idle = -1.0
    for dev in trace.devices.values():
        for name, secs in self_times(clip(dev.ops, window)).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + secs
        gaps = idle_gaps(dev, window)
        idle = sum(b - a for a, b in gaps)
        if idle > worst_idle:
            worst_idle, worst_gaps = idle, gaps
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": [[n, s] for n, s in name_gaps(worst_gaps, trace.host, top)],
    }


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))

"""Share of the device's busy time spent in operations under one of the
program's ``jax.named_scope``s.

The TPU's trace names an operation by its HLO text, which starts with the
instruction's name (``%fusion.12 = ...``) and carries no source path.  The
driver reads the loaded programs' own text while they are loaded
(``run.facts["op_sources"]``: program name, instruction name, the path it
was traced under), and an event is put to its program by the interval of
the ``XLA Modules`` line that holds it.  An operation belongs to
``spec["scope"]`` where its path holds the scope as a component.  Time is
self time: a container (a loop, a call) counts only what its children
leave, so a scope's operations inside a ``while`` are counted once.  None
where the driver gave no sources or none holds the scope (a program
without it)."""

import bisect
import re

from benchmark import trace as trace_lib


def self_seconds(events):
    """[(name, start, self seconds)] for one line's events: an event's
    time less that of the events nested inside it."""
    done, stack = [], []  # stack of [name, start, end, own]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, _, own = stack.pop()
            done.append((name, start, max(own, 0.0)))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][3] -= dur
        stack.append([name, start, start + dur, dur])
    close(float("inf"))
    return done


def sourced(dev, window, sources):
    """[(source path or "", self seconds)] for a device's operations in
    ``window``."""
    modules = sorted(
        (start, start + dur, name.split("(")[0])
        for name, start, dur in trace_lib.clip(dev.modules, window)
    )
    starts = [m[0] for m in modules]
    out = []
    for name, start, own in self_seconds(trace_lib.clip(dev.ops, window)):
        i = bisect.bisect_right(starts, start) - 1
        program = modules[i][2] if i >= 0 and start < modules[i][1] else ""
        instruction = name.split(" = ")[0].lstrip("%")
        out.append((sources.get(program, {}).get(instruction, ""), own))
    return out


def of_run(run):
    if run.trace is None or not run.facts.get("op_sources"):
        return None
    if "scope_self_seconds" not in run.facts:
        run.facts["scope_self_seconds"] = [
            pair for dev in run.trace.devices.values()
            for pair in sourced(dev, run.trace_window, run.facts["op_sources"])
        ]
    return run.facts["scope_self_seconds"] or None


def read(spec: dict, run):
    pairs = of_run(run)
    if not pairs:
        return None
    inside = re.compile(r"(^|[/(])" + re.escape(spec["scope"]) + r"(/|\)|$)")
    total = sum(s for _, s in pairs)
    under = sum(s for source, s in pairs if inside.search(source))
    found = sum(s for source, s in pairs if source)
    print(f"[bench] scope {spec['scope']}: {under:.6f}s of {total:.6f}s busy; "
          f"{found:.6f}s of it has a source path", flush=True)
    if under <= 0.0 or total <= 0.0:
        return None
    return 100.0 * under / total

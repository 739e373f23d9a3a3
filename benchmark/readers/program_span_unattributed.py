"""Share of the window's device idle time that no span of the program
explains: idle of the idlest device whose innermost covering span, on the
thread that drives the device (``program_spans.idle_by_span``), is one of
``roots`` or none.  Prints the idle seconds by span."""

from benchmark import program_spans


def read(spec: dict, run):
    lines = program_spans.of_run(run)
    if not lines or not run.trace.devices:
        return None
    if not program_spans.lines_holding(lines, spec["launch"]):
        return None
    gaps = program_spans.idlest_gaps(run.trace, run.trace_window)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0.0:
        return None
    by_span = program_spans.idle_by_span(
        gaps, lines, spec["launch"], spec.get("starter")
    )
    print("[bench] idle by program span: " + ", ".join(
        f"{name or '(none)'} {secs:.4f}s"
        for name, secs in sorted(by_span.items(), key=lambda kv: -kv[1])
        if secs > 0.0
    ), flush=True)
    unexplained = by_span[""] + sum(by_span.get(r, 0.0) for r in spec["roots"])
    return 100.0 * unexplained / idle

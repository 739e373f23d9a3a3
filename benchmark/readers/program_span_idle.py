"""Device idle time inside the program's spans of one name, per such
span, in milliseconds: the idle gaps of the idlest device that fall
inside ``dml:<span>`` (any thread)."""

from benchmark import program_spans


def read(spec: dict, run):
    lines = program_spans.of_run(run)
    if not lines or not run.trace.devices:
        return None
    spans = program_spans.named(lines, spec["span"])
    if not spans:
        return None
    gaps = program_spans.idlest_gaps(run.trace, run.trace_window)
    return 1e3 * program_spans.overlap_seconds(gaps, spans) / len(spans)

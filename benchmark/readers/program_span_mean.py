"""Mean duration of the program's spans of one name (``dml:<span>`` in
the profiler's host plane, any thread), in ``units_per_s`` a second
(1e3: milliseconds); with ``per``, their summed duration over their
summed attr ``per``, a cost per item."""

from benchmark import program_spans


def read(spec: dict, run):
    lines = program_spans.of_run(run)
    if not lines:
        return None
    seconds = program_spans.mean_seconds(
        program_spans.named(lines, spec["span"]), spec.get("per")
    )
    if seconds is None:
        return None
    return float(spec.get("units_per_s", 1e3)) * seconds

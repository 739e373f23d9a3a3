"""Idle share of the traced window on the busiest device: 1 minus the
union of the intervals in which an operation ran."""

from benchmark import trace as trace_lib


def read(spec: dict, run):
    if run.trace is None or not run.trace.devices:
        return None
    window_s = run.trace_window[1] - run.trace_window[0]
    busy = max(
        trace_lib.busy_seconds(dev, run.trace_window)
        for dev in run.trace.devices.values()
    )
    return 100.0 * (1.0 - busy / window_s)

"""Mean idle gap, in milliseconds, between successive device programs whose
name holds ``match`` (other programs' device time between them taken out),
over the devices of the trace."""

from benchmark import trace as trace_lib


def read(spec: dict, run):
    if run.trace is None:
        return None
    gaps = []
    for dev in run.trace.devices.values():
        gaps.extend(trace_lib.module_gaps(dev, run.trace_window, spec["match"]))
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)

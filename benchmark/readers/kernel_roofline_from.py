"""``kernel_roofline`` with the cost function's module named by the
metric's file (``cost_module``), for kernels whose operations and bytes
another configuration's shapes decide: the least time the chip could take
for the calls seen over the summed device time of the kernel's events."""

import importlib

from benchmark import flops
from benchmark import trace as trace_lib


def read(spec: dict, run):
    call = run.facts.get("attention_call")
    if run.trace is None or not call or not run.peaks:
        return None
    seconds, events = 0.0, 0
    for dev in run.trace.devices.values():
        s, n = trace_lib.kernel_seconds(dev, run.trace_window, **spec["match"])
        seconds += s
        events += n
    if not events or seconds <= 0.0:
        return None
    calls = events / float(spec.get("events_per_call", 1))
    cost = getattr(importlib.import_module(spec["cost_module"]), spec["cost"])
    ops, nbytes = cost(**call)
    floor_s, bound = flops.roofline_floor_s(ops, nbytes, run.peaks)
    print(f"[bench] {spec['cost']}: {events} events, {seconds:.6f}s on the "
          f"device, floor {floor_s * calls:.6f}s, bound by {bound}",
          flush=True)
    return 100.0 * floor_s * calls / seconds

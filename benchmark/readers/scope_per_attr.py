"""Device time of one ``jax.named_scope`` inside one program, per item the
program's spans count: the self time of the operations of program
``spec["program"]`` (the ``XLA Modules`` name, ``jit_epoch``) whose source
path holds ``spec["scope"]``, as ``scope_share`` puts them, over the sum of
attr ``spec["attr"]`` of the spans ``spec["span"]`` in the same traced
window; in ``units_per_s`` a second (1e9: nanoseconds an item).  None
where no span carries the attr, or the program ran no operation of the
scope."""

import re

from benchmark import program_spans
from benchmark.readers import scope_share


def read(spec: dict, run):
    lines = program_spans.of_run(run)
    if not lines or not run.facts.get("op_sources"):
        return None
    items = sum(float(s.attrs[spec["attr"]])
                for s in program_spans.named(lines, spec["span"])
                if spec["attr"] in s.attrs)
    program = spec["program"]
    # Sources of the one program: every other program's operations find
    # no path and fall outside the scope.
    only = {program: run.facts["op_sources"].get(program, {})}
    inside = re.compile(r"(^|[/(])" + re.escape(spec["scope"]) + r"(/|\)|$)")
    under = sum(
        own for dev in run.trace.devices.values()
        for source, own in scope_share.sourced(dev, run.trace_window, only)
        if inside.search(source)
    )
    print(f"[bench] scope {spec['scope']} in {program}: {under:.6f}s over "
          f"{items:g} {spec['attr']}", flush=True)
    if items <= 0.0 or under <= 0.0:
        return None
    return float(spec.get("units_per_s", 1.0)) * under / items

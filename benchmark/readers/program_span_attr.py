"""A quantity the program puts on its spans of one name (``dml:<span>``
in the profiler's host plane, any thread, inside the traced window): the
sum of attr ``value`` over the sum of attr ``per``.  With ``weight``, each
span's ``value`` counts ``weight`` times, so ``value`` = a mean over the
span's items, ``weight`` = ``per`` = its item count gives the mean over
all items.  None where no span carries ``value`` (a program that puts no
such attr on the span)."""

from benchmark import program_spans


def read(spec: dict, run):
    lines = program_spans.of_run(run)
    if not lines:
        return None
    spans = [s for s in program_spans.named(lines, spec["span"])
             if spec["value"] in s.attrs]
    weight = spec.get("weight")
    total = sum(float(s.attrs[spec["value"]])
                * (float(s.attrs.get(weight, 0)) if weight else 1.0)
                for s in spans)
    per = sum(float(s.attrs.get(spec["per"], 0)) for s in spans)
    print(f"[bench] span {spec['span']}: {spec['value']} on {len(spans)} "
          f"spans, {total:g} over {per:g} {spec['per']}", flush=True)
    if not spans or per <= 0.0:
        return None
    return total / per

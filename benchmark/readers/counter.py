"""A counter of the program, after the window minus before it."""


def read(spec: dict, run):
    return run.counters.get(spec["counter"])

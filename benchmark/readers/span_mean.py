"""Mean of the benchmark's own host spans of one name, in milliseconds."""


def read(spec: dict, run):
    spans = run.spans.get(spec["span"]) or []
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)

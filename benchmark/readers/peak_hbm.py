"""Peak bytes in use on the fullest device after the window, over its
limit."""


def read(spec: dict, run):
    if not run.memory_limit_bytes:
        return None
    return 100.0 * run.memory_peak_bytes / run.memory_limit_bytes

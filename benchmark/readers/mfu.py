"""The whole step's share of the chip's peak: the matrix-product
operations that the forward and backward passes of the traced window's
work need (``facts[spec["flops"]]``, recomputation not counted) over the
traced window, the chips and the peak bfloat16 rate."""


def read(spec: dict, run):
    flops = run.facts.get(spec["flops"])
    if run.trace is None or not flops or not run.peaks:
        return None
    window_s = run.trace_window[1] - run.trace_window[0]
    peak = run.peaks["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * flops / (window_s * peak)

"""Plain asynchronous successive halving (Li et al. 2018), for replaying a
sweep's decisions from the losses that its trials reported.

Rungs at ``grace * eta**k`` up to ``max_t``.  A trial that reaches a rung
records its loss there and goes on only if that loss is within the best
``1/eta`` of the losses recorded at the rung so far (with fewer than
``eta`` of them it goes on); at ``max_t`` it ends.  Lower is better.
"""

from __future__ import annotations

from typing import Dict, List, Sequence


def rungs(max_t: int, grace: int, eta: float) -> List[int]:
    out, r = [], float(grace)
    while r <= max_t:
        out.append(int(r))
        r *= eta
    return out


def replay(curves: Sequence[Sequence[float]], *, max_t: int, grace: int,
           eta: float) -> List[int]:
    """How many epochs each trial runs when the trials report epoch by
    epoch in their given order (a population reports its live trials in row
    order after every epoch).  ``curves[i][e]`` is trial i's loss after
    epoch e+1, as far as it is known; a trial that would go on past what is
    known ends there."""
    levels = rungs(max_t, grace, eta)
    recorded: Dict[int, List[float]] = {r: [] for r in levels}
    next_rung = [0] * len(curves)
    length = [0] * len(curves)
    live = [True] * len(curves)
    for epoch in range(1, max_t + 1):
        for i, curve in enumerate(curves):
            if not live[i]:
                continue
            if len(curve) < epoch:
                live[i] = False
                continue
            length[i] = epoch
            if epoch >= max_t:
                live[i] = False
                continue
            k = next_rung[i]
            if k >= len(levels) or epoch < levels[k]:
                continue
            while k + 1 < len(levels) and epoch >= levels[k + 1]:
                k += 1
            next_rung[i] = k + 1
            scores = recorded[levels[k]]
            scores.append(curve[epoch - 1])
            keep = int(len(scores) / eta)
            if keep >= 1 and curve[epoch - 1] > sorted(scores)[keep - 1]:
                live[i] = False
    return length

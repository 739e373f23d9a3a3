"""Step-stream anomaly detection: robust z-scores over per-step timings.

The obs plane records *where* time went; this module watches *whether any
of it was abnormal* — the fail-slow shapes every postmortem in this repo
shares (a degraded link that doubles step time, a CPU-starved producer
that starves one trial, one gang member 3x slower than its peers):

* :class:`StepAnomalyDetector` — per-program-key sliding windows of step
  durations judged by **median/MAD robust z-score** (mean/std would let
  the outliers being hunted drag the threshold toward themselves).  The
  feeders: both trainables' per-epoch timings (per-trial outliers in a
  sweep — the window is shared across trials of one program class, the
  observation is attributed to a trial id), and the serve plane's
  ``engine.step`` flushes via the continuous batcher's existing per-
  bucket EWMA loop (``serve/batcher.py``).
* :class:`GangSkewMonitor` — per-round, per-member timings of one
  process-spanning trial (``multihost.check_gang_skew`` allgathers each
  member's epoch wall); a member sustained above the peer median is a
  named straggler.

A single outlier increments ``perf_anomaly_events``; ``sustain``
consecutive anomalies from the SAME attribution increment
``perf_anomaly_sustained`` plus a per-culprit counter
(``perf_straggler[<who>]`` — the trial or process id IS in the counter
name) and trigger one flight-recorder dump naming the slow member/trial
(``obs.dump_flight_recorder``).  Detection must never raise into a hot
path; every surface here is telemetry-grade.

Stdlib-only (no jax, no numpy): importable from the linter and serve
plane alike.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from distributed_machine_learning_tpu.analysis.locks import named_lock

DEFAULT_WINDOW = 64
DEFAULT_Z_THRESHOLD = 4.0
DEFAULT_SUSTAIN = 3
MIN_SAMPLES = 5

# 0.6745 ~= Phi^-1(0.75): scales MAD to the sigma of a normal, the
# standard robust-z convention (Iglewicz & Hoaglin).
_MAD_SCALE = 0.6745


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


class RobustWindow:
    """A bounded window of recent durations with median/MAD z-scores.

    Bounded by construction (``deque(maxlen=...)``): a detector that
    accumulated every step of a month-long soak would be the PR 8
    ring-buffer bug wearing a new hat (dmlint DML017)."""

    def __init__(self, capacity: int = DEFAULT_WINDOW):
        if capacity < MIN_SAMPLES:
            raise ValueError(
                f"capacity must be >= {MIN_SAMPLES}: {capacity}"
            )
        self._vals: deque = deque(maxlen=int(capacity))

    def add(self, value: float) -> None:
        self._vals.append(float(value))

    def __len__(self) -> int:
        return len(self._vals)

    def median(self) -> Optional[float]:
        return _median(list(self._vals)) if self._vals else None

    def zscore(self, value: float) -> Optional[float]:
        """Robust z of ``value`` vs the window (None below MIN_SAMPLES).
        A zero MAD (near-identical timings) falls back to a 5%-of-median
        scale so a genuinely flat stream still scores a 2x step as
        anomalous instead of dividing by zero."""
        vals = list(self._vals)
        if len(vals) < MIN_SAMPLES:
            return None
        med = _median(vals)
        mad = _median([abs(v - med) for v in vals])
        # The floor keeps a degenerate window (near-zero median from
        # clamped measurements) from manufacturing astronomic z-scores:
        # below it, nothing is judged anomalous by a sub-microsecond gap.
        scale = mad / _MAD_SCALE if mad > 0 else max(
            abs(med) * 0.05, 1e-6
        )
        return (float(value) - med) / scale


class StepAnomalyDetector:
    """Windowed per-key anomaly detection with sustained-culprit naming.

    ``observe(key, seconds, who=...)`` returns an anomaly dict for a
    SLOW outlier (fast outliers are left alone — the hunt is for
    stragglers, and a suspiciously fast step shows up in correctness
    tests, not here), and None otherwise.
    """

    def __init__(
        self,
        window: int = DEFAULT_WINDOW,
        z_threshold: float = DEFAULT_Z_THRESHOLD,
        sustain: int = DEFAULT_SUSTAIN,
    ):
        self.window = int(window)
        self.z_threshold = float(z_threshold)
        self.sustain = max(int(sustain), 1)
        self._lock = named_lock("perf.anomaly")
        self._windows: Dict[str, RobustWindow] = {}
        self._streaks: Dict[Tuple[str, Optional[str]], int] = {}
        self.anomalies = 0
        self.sustained = 0
        self.observations = 0

    def observe(
        self, key: str, seconds: float, who: Optional[str] = None
    ) -> Optional[Dict[str, Any]]:
        from distributed_machine_learning_tpu import obs

        try:
            with self._lock:
                self.observations += 1
                w = self._windows.get(key)
                if w is None:
                    w = self._windows[key] = RobustWindow(self.window)
                z = w.zscore(seconds)
                med = w.median()
                w.add(seconds)
                streak_key = (key, who)
                if z is not None and z >= self.z_threshold:
                    self.anomalies += 1
                    streak = self._streaks.get(streak_key, 0) + 1
                    self._streaks[streak_key] = streak
                    if streak == self.sustain:
                        self.sustained += 1
                else:
                    self._streaks.pop(streak_key, None)
                    return None
            reg = obs.get_registry()
            reg.add("perf_anomaly_events")
            anomaly = {
                "program": key,
                "who": who,
                "seconds": round(float(seconds), 6),
                "median_s": round(med, 6) if med is not None else None,
                "zscore": round(z, 2),
                "streak": streak,
                "sustained": streak >= self.sustain,
            }
            obs.event("perf_anomaly", anomaly)
            if streak == self.sustain:
                # Fire the heavy forensics ONCE per streak (the streak
                # counter keeps growing, the dump does not repeat).
                reg.add("perf_anomaly_sustained")
                if who is not None:
                    reg.add(f"perf_straggler[{who}]")
                obs.dump_flight_recorder(
                    f"perf_anomaly_{key}", extra=anomaly
                )
            return anomaly
        except Exception:  # noqa: BLE001 - never fail the timed hot path
            obs.get_registry().add("perf_anomaly_errors")
            return None

    def snapshot(self) -> Dict[str, float]:
        """The ``perf`` registry family: detector health at a glance."""
        with self._lock:
            return {
                "observations": self.observations,
                "anomalies": self.anomalies,
                "sustained": self.sustained,
                "programs_watched": len(self._windows),
            }

    def reset(self) -> None:
        """Test hook: drop every window and streak."""
        with self._lock:
            self._windows.clear()
            self._streaks.clear()
            self.anomalies = self.sustained = self.observations = 0


def skew_by_member(
    values: Dict[Any, float], ratio_threshold: float = 1.75
) -> List[Tuple[Any, float]]:
    """Members whose timing exceeds ``ratio_threshold`` x the median of
    their PEERS (median excludes the candidate, so one straggler in a
    2-member gang is still visible).  Returns ``[(member, ratio), ...]``
    sorted slowest-first; empty for a healthy round."""
    if len(values) < 2:
        return []
    out: List[Tuple[Any, float]] = []
    for member, v in values.items():
        peers = [x for m, x in values.items() if m != member]
        med = _median(peers)
        if med <= 0:
            continue
        ratio = float(v) / med
        if ratio >= ratio_threshold:
            out.append((member, round(ratio, 3)))
    out.sort(key=lambda t: t[1], reverse=True)
    return out


class GangSkewMonitor:
    """Sustained per-gang-member skew over successive rounds (epochs).

    Pure bookkeeping — the collectives that gather each member's timing
    live in ``multihost.runtime.check_gang_skew``; this class just
    judges the per-round ``{process_id: seconds}`` map so it is testable
    without a process-spanning runtime."""

    def __init__(
        self,
        ratio_threshold: float = 1.75,
        sustain: int = 2,
        gang_id: Optional[str] = None,
    ):
        self.ratio_threshold = float(ratio_threshold)
        self.sustain = max(int(sustain), 1)
        self.gang_id = gang_id
        self._lock = named_lock("perf.gangskew")
        self._streaks: Dict[Any, int] = {}
        self.rounds = 0
        self.straggler_rounds = 0

    def observe_round(
        self,
        values: Dict[Any, float],
        label: str = "epoch",
        report: bool = True,
    ) -> List[Tuple[Any, float]]:
        """Judge one round; ``report=False`` (non-coordinator gang
        members) still tracks streaks but leaves counters and dumps to
        the coordinator so the head sees each incident exactly once."""
        from distributed_machine_learning_tpu import obs

        stragglers = skew_by_member(values, self.ratio_threshold)
        newly_sustained = []
        with self._lock:
            self.rounds += 1
            if stragglers:
                self.straggler_rounds += 1
            flagged = {m for m, _ in stragglers}
            for m in list(self._streaks):
                if m not in flagged:
                    self._streaks.pop(m)
            for m, ratio in stragglers:
                streak = self._streaks.get(m, 0) + 1
                self._streaks[m] = streak
                if streak == self.sustain:
                    newly_sustained.append((m, ratio))
        if report and newly_sustained:
            reg = obs.get_registry()
            for member, ratio in newly_sustained:
                reg.add("perf_anomaly_sustained")
                reg.add(f"perf_straggler[process_{member}]")
                detail = {
                    "label": label,
                    "gang_id": self.gang_id,
                    "process_id": member,
                    "ratio_vs_peer_median": ratio,
                    "round_timings_s": {
                        str(k): round(float(v), 6)
                        for k, v in values.items()
                    },
                }
                obs.event("perf_gang_skew", detail)
                obs.dump_flight_recorder(
                    f"perf_gang_skew_p{member}", extra=detail
                )
        return stragglers

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "rounds": self.rounds,
                "straggler_rounds": self.straggler_rounds,
            }


_detector: Optional[StepAnomalyDetector] = None
_detector_lock = threading.Lock()  # creation only


def get_step_anomalies() -> StepAnomalyDetector:
    """The process-wide detector (registered as the ``perf`` family in
    the metrics registry, same discipline as ``compilecache.counters``)."""
    global _detector
    if _detector is None:
        with _detector_lock:
            if _detector is None:
                det = StepAnomalyDetector()
                from distributed_machine_learning_tpu.obs import (
                    get_registry,
                )

                get_registry().register_family("perf", det)
                _detector = det
    return _detector

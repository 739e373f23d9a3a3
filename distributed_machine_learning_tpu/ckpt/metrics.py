"""Process-wide checkpoint I/O counters.

Orbax's position (PAPERS.md) is that checkpoint save/restore time is a
first-order training cost — which makes it a first-order *metric*: a sweep
that stalls behind synchronous writes should show it in numbers, not in a
hunch.  One registry for the whole process (both checkpoint formats, every
driver) so the runner/cluster/vectorized teardowns can publish a
``checkpoint`` block into ``experiment_state.json`` and TensorBoard next to
the liveness and fault counters.

Drivers scope the process-wide totals to one run by snapshotting at start
and writing :meth:`CheckpointMetrics.delta_since` at teardown.

The async-overlap accounting is counter-based (no clocks): every report
boundary calls :func:`note_step`; an async save records the step counter at
submit and, when its write completes, the steps that elapsed in between —
``async_overlapped_steps`` > 0 is the proof that training ran while the
write was in flight.
"""

from __future__ import annotations

import threading
from typing import Dict
from distributed_machine_learning_tpu.analysis.locks import named_lock


class CheckpointMetrics:
    """Thread-safe counter registry for checkpoint save/restore activity."""

    _FIELDS = (
        "saves",
        "saves_streamed",
        "save_bytes",
        "save_wall_s",
        "save_block_s",
        "snapshot_dispatches",
        "snapshot_leaves",
        "chunks_written",
        "save_errors",
        "async_saves",
        "async_saves_overlapping",
        "async_overlapped_steps",
        "steps",
        "restores",
        "restore_bytes",
        "restore_wall_s",
        "restore_fallbacks",
        "corrupt_generations_skipped",
        "uncommitted_cleaned",
        "generations_pruned",
    )

    def __init__(self):
        self._lock = named_lock("ckpt.metrics")
        self._c: Dict[str, float] = {k: 0 for k in self._FIELDS}

    def add(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._c[name] = self._c.get(name, 0) + value

    def note_step(self) -> int:
        """One training step boundary passed; returns the new step count.
        Called at every report/dispatch boundary by the drivers."""
        with self._lock:
            self._c["steps"] += 1
            return int(self._c["steps"])

    def step_count(self) -> int:
        with self._lock:
            return int(self._c["steps"])

    def record_save(self, wall_s: float, nbytes: int, chunks: int = 1,
                    streamed: bool = False) -> None:
        """One save done.  ``streamed``: a msgpack blob that went from the
        leaves to storage without the payload being built (a tree the
        streamer does not cover is packed whole and counts in ``saves``
        alone)."""
        with self._lock:
            self._c["saves"] += 1
            self._c["saves_streamed"] += bool(streamed)
            self._c["save_wall_s"] += wall_s
            self._c["save_bytes"] += nbytes
            self._c["chunks_written"] += chunks

    def record_restore(self, wall_s: float, nbytes: int) -> None:
        with self._lock:
            self._c["restores"] += 1
            self._c["restore_wall_s"] += wall_s
            self._c["restore_bytes"] += nbytes

    def record_async_completion(self, steps_at_submit: int) -> None:
        """An async write became durable; credit the training steps that
        happened while it was in flight."""
        with self._lock:
            overlapped = max(int(self._c["steps"]) - steps_at_submit, 0)
            self._c["async_saves"] += 1
            self._c["async_overlapped_steps"] += overlapped
            if overlapped > 0:
                self._c["async_saves_overlapping"] += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in self._c.items()
            }

    def delta_since(self, baseline: Dict[str, float]) -> Dict[str, float]:
        """Counters accumulated since ``baseline`` (a prior snapshot) —
        how a driver scopes the process-wide registry to one run."""
        snap = self.snapshot()
        return {
            k: round(v - baseline.get(k, 0), 4)
            for k, v in snap.items()
        }

    def reset(self) -> None:
        """Test hook: zero every counter."""
        with self._lock:
            self._c = {k: 0 for k in self._FIELDS}


_metrics = CheckpointMetrics()

# The unified observability plane sees the same counters (obs/registry.py):
# the blocks drivers publish stay byte-identical, this just makes them
# visible in one place (flight dumps, /metrics "obs", head aggregation).
from distributed_machine_learning_tpu.obs.registry import (  # noqa: E402
    get_registry as _obs_registry,
)

_obs_registry().register_family("checkpoint", _metrics)


def get_metrics() -> CheckpointMetrics:
    """The process-wide registry (one per process, like the compile
    tracker in ``utils/compile_cache.py``)."""
    return _metrics


def note_step() -> int:
    return _metrics.note_step()

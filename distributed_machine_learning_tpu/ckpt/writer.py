"""Async checkpoint writer: training overlaps checkpoint I/O.

Orbax-style split (PAPERS.md): ``save()`` does only the device->host
snapshot on the calling thread — per-shard, so a sharded array is never
gathered — and returns; serialization, hashing, chunk writes, and the
COMMIT marker all run on one background thread in submission order.  The
caller's next training step runs concurrently with the write.

Error contract: a failed write surfaces on the NEXT ``save()`` (and on
``wait_until_finished()``) as the original exception — a sweep that keeps
checkpointing into a dead filesystem fails at the next save boundary
instead of silently training past its last durable state.

Overlap accounting is counter-based (``ckpt.metrics``): submit records the
global step counter; completion credits the steps that elapsed while the
write was in flight.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, List, Optional, Tuple

from distributed_machine_learning_tpu.ckpt import format as fmt
from distributed_machine_learning_tpu.ckpt.metrics import get_metrics
from distributed_machine_learning_tpu.analysis.locks import named_lock


class AsyncCheckpointer:
    """One background writer; submission order is write order."""

    def __init__(self, log: Optional[Callable[[str], None]] = None):
        self._q: "queue.Queue" = queue.Queue()
        self._lock = named_lock("ckpt.writer")
        self._pending: List[Tuple[str, threading.Event]] = []
        self._error: Optional[BaseException] = None
        self._error_path: Optional[str] = None
        self._log = log or (
            lambda msg: print(f"[ckpt] {msg}", flush=True)
        )
        self._thread = threading.Thread(
            target=self._worker, name="ckpt-async-writer", daemon=True
        )
        self._thread.start()

    def _worker(self):
        metrics = get_metrics()
        while True:
            item = self._q.get()
            if item is None:
                return
            path, skeleton, leaves, done, steps_at_submit = item
            try:
                import time as _time

                from distributed_machine_learning_tpu import obs

                t0 = _time.time()
                with obs.span("ckpt.save_async", {"path": path}):
                    nbytes, nchunks = fmt.write_snapshot(
                        path, skeleton, leaves
                    )
                metrics.record_save(
                    _time.time() - t0, nbytes, max(nchunks, 1)
                )
                metrics.record_async_completion(steps_at_submit)
            except BaseException as exc:  # noqa: BLE001 - surfaced on next save
                metrics.add("save_errors")
                with self._lock:
                    self._error = exc
                    self._error_path = path
            finally:
                with self._lock:
                    self._pending = [
                        (p, ev) for p, ev in self._pending if ev is not done
                    ]
                done.set()

    def _raise_pending_error(self):
        with self._lock:
            exc, path = self._error, self._error_path
            self._error, self._error_path = None, None
        if exc is not None:
            raise RuntimeError(
                f"previous async checkpoint save to {path} failed"
            ) from exc

    def save(self, path: str, tree) -> str:
        """Snapshot ``tree`` to host NOW (per-shard; donation-safe) and
        queue the write; returns ``path`` immediately.  Raises the previous
        save's error, if any, before doing anything."""
        self._raise_pending_error()
        import time as _time

        from distributed_machine_learning_tpu import obs

        t0 = _time.time()
        with obs.span("ckpt.device_get"):
            skeleton, leaves = fmt.snapshot_tree(tree)
        metrics = get_metrics()
        metrics.add("save_block_s", _time.time() - t0)
        done = threading.Event()
        with self._lock:
            self._pending.append((path, done))
        self._q.put((path, skeleton, leaves, done, metrics.step_count()))
        return path

    def wait_until_finished(self, timeout: Optional[float] = None) -> bool:
        """Barrier: block until every queued write is durable; re-raise the
        first unclaimed write error.  Returns False on timeout."""
        import time as _time

        # Monotonic: this is a wait DEADLINE — a wall-clock step must not
        # stretch or collapse the barrier (dmlint DML004).
        deadline = None if timeout is None else _time.monotonic() + timeout
        with self._lock:
            events = [ev for _, ev in self._pending]
        for ev in events:
            left = None if deadline is None else deadline - _time.monotonic()
            if left is not None and left <= 0:
                return False
            if not ev.wait(left):
                return False
        self._raise_pending_error()
        return True

    def pending_paths(self) -> List[str]:
        with self._lock:
            return [p for p, _ in self._pending]

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Flush (bounded) and stop the worker; unclaimed errors are logged
        rather than lost."""
        if not self._thread.is_alive():
            return
        try:
            flushed = self.wait_until_finished(timeout=timeout)
        except BaseException as exc:  # noqa: BLE001 - teardown must not die
            self._log(f"WARNING: async checkpoint write failed: {exc!r}")
            flushed = True
        if not flushed:
            self._log(
                f"WARNING: abandoning hung checkpoint write(s) at "
                f"teardown: {self.pending_paths()[:3]}"
            )
        self._q.put(None)
        if flushed:
            self._thread.join(timeout=10)

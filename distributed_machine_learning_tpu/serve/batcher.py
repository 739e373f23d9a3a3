"""Request batching in front of an inference engine.

Individual ``/predict`` requests are tiny; dispatching each alone wastes
the accelerator (a batch-1 program moves the same weights through the chip
as a batch-64 one).  Two batchers share one contract (``submit`` returns a
``concurrent.futures.Future`` resolving to the caller's own rows of the
batched result; arrival order is preserved within a flush):

* :class:`MicroBatcher` — the original two-trigger policy: flush at
  ``max_batch_size`` rows OR when the oldest request has waited
  ``max_latency_ms``.  Simple, but timer-bound: under burst the partial
  flush waits out the timer while the device idles.
* :class:`ContinuousBatcher` — inflight (continuous) batching: the worker
  never waits on a timer.  While one flush executes on the device,
  arrivals coalesce; the moment the engine frees up the next flush takes
  everything queued, up to a cap sized from the engine's bucket grid and
  the per-bucket *measured* step time.  A lone request dispatches
  immediately (batch-1 latency = one step, no ``max_latency_ms`` floor)
  and a deep queue rides out in near-full batches — the device is
  saturated whenever work exists (the Podracer keep-the-device-busy
  principle applied to serving).  Its queue is **bounded**: past
  ``max_queue`` pending requests ``submit`` raises :class:`QueueFull`
  (carrying a ``retry_after_s`` estimate from the measured step time),
  which the HTTP layer turns into 429 + Retry-After — admission control
  instead of an OOM under overload.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.perf.anomaly import (
    get_step_anomalies,
)


class BatcherStopped(RuntimeError):
    """The batcher's worker is gone (kill/drain) — the request was never
    flushed.  ``ReplicaSet.predict`` treats this as a replica death and
    redispatches to a survivor instead of failing the client."""


class QueueFull(RuntimeError):
    """Admission refused: the bounded request queue is at capacity.

    ``retry_after_s`` estimates when capacity frees up (queue depth x
    measured step time over the batch cap) — the HTTP layer forwards it
    as a 429 Retry-After header instead of letting the queue grow."""

    def __init__(self, depth: int, max_queue: int, retry_after_s: float):
        super().__init__(
            f"request queue full ({depth}/{max_queue}); retry in "
            f"{retry_after_s:.2f}s"
        )
        self.depth = depth
        self.max_queue = max_queue
        self.retry_after_s = retry_after_s


@dataclass
class _Pending:
    x: np.ndarray
    future: Future
    # Monotonic: feeds the max_latency flush deadline (dmlint DML004).
    enqueued_at: float = field(default_factory=time.monotonic)
    # Submitter's span context (serve.request/serve.predict): the flush
    # span on the batcher thread parents under it, so one request's trace
    # crosses the queue boundary (None when tracing is off — free).
    obs_ctx: object = field(default_factory=obs.current_context)


class BatcherStats:
    """Thread-safe flush accounting (fill ratio, trigger mix, depth)."""

    def __init__(self):
        self._lock = named_lock("serve.batcher.stats")
        self.batches = 0
        self.rows = 0
        self.size_flushes = 0
        self.latency_flushes = 0

    def record(self, rows: int, trigger: str):
        with self._lock:
            self.batches += 1
            self.rows += rows
            if trigger == "size":
                self.size_flushes += 1
            else:
                self.latency_flushes += 1

    def to_dict(self, max_batch_size: int) -> Dict[str, Any]:
        with self._lock:
            fill = (
                self.rows / (self.batches * max_batch_size)
                if self.batches
                else 0.0
            )
            return {
                "batches": self.batches,
                "rows": self.rows,
                "batch_fill_ratio": round(fill, 4),
                "size_flushes": self.size_flushes,
                "latency_flushes": self.latency_flushes,
            }


class MicroBatcher:
    """Background flush loop feeding ``infer_fn`` coalesced batches.

    ``infer_fn(batch) -> predictions`` is called on the batcher's worker
    thread, one flush at a time; an exception fails every request in that
    flush (each future gets it) and the loop keeps serving — one poisoned
    batch must not take the replica down.
    """

    def __init__(
        self,
        infer_fn: Callable[[np.ndarray], np.ndarray],
        max_batch_size: int = 64,
        max_latency_ms: float = 5.0,
        name: str = "batcher",
    ):
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1: {max_batch_size}")
        self.infer_fn = infer_fn
        self.max_batch_size = int(max_batch_size)
        self.max_latency_s = float(max_latency_ms) / 1000.0
        self.stats = BatcherStats()
        self._queue: List[_Pending] = []
        # NamedLock ducks the lock protocol threading.Condition needs.
        self._lock = named_lock("serve.batcher.queue")
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True
        )
        self._thread.start()

    # -- producer side -------------------------------------------------------

    def submit(self, x) -> Future:
        """Enqueue one request; resolves to its rows of the batched output."""
        x = np.asarray(x)
        fut: Future = Future()
        with self._wake:
            if self._stop:
                fut.set_exception(BatcherStopped("batcher is stopped"))
                return fut
            self._queue.append(_Pending(x, fut))
            self._wake.notify()
        return fut

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def is_alive(self) -> bool:
        # dmlint: disable=unguarded-shared-state deliberate lock-free read: alive() sits on the per-request dispatch path and a single bool load is atomic under the GIL — staleness only delays failover by one round-robin pass
        return self._thread.is_alive() and not self._stop

    # -- worker side ---------------------------------------------------------

    def _take_batch(self) -> Optional[List[_Pending]]:
        """Block until a flush trigger fires (or stop); returns the drained
        requests for one batch."""
        with self._wake:
            while True:
                if self._stop and not self._queue:
                    return None
                if self._queue:
                    rows = sum(p.x.shape[0] for p in self._queue)
                    oldest = self._queue[0].enqueued_at
                    now = time.monotonic()
                    if self._stop or rows >= self.max_batch_size:
                        return self._drain("size")
                    remaining = self.max_latency_s - (now - oldest)
                    if remaining <= 0:
                        return self._drain("latency")
                    self._wake.wait(timeout=remaining)
                else:
                    self._wake.wait(timeout=0.1)

    def _drain(self, trigger: str) -> List[_Pending]:
        # Called under the lock. Take whole requests up to the size cap —
        # never split one request across flushes (its future maps 1:1 to a
        # contiguous slice of ONE engine call); a single over-cap request
        # flushes alone and the engine chunks it internally.
        batch: List[_Pending] = []
        rows = 0
        while self._queue:
            nxt = self._queue[0]
            n = nxt.x.shape[0]
            if batch and rows + n > self.max_batch_size:
                break
            batch.append(self._queue.pop(0))
            rows += n
        self.stats.record(rows, trigger)
        return batch

    def _loop(self):
        from distributed_machine_learning_tpu.utils.heartbeat import (
            touch_heartbeat,
        )

        while True:
            batch = self._take_batch()
            if batch is None:
                return
            try:
                xs = np.concatenate([p.x for p in batch], axis=0)
                with obs.span(
                    "batch.flush",
                    {"rows": int(xs.shape[0]), "requests": len(batch)},
                    parent=batch[0].obs_ctx,
                ):
                    preds = np.asarray(self.infer_fn(xs))
                off = 0
                for p in batch:
                    n = p.x.shape[0]
                    p.future.set_result(preds[off: off + n])
                    off += n
            except BaseException as exc:  # noqa: BLE001 - fail the batch only
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(exc)
            # A completed flush is real progress — same contract as the
            # trainables' phase boundaries (utils/heartbeat.py).
            touch_heartbeat()

    def stop(self, drain: bool = True, timeout: float = 5.0):
        """Stop the worker; with ``drain`` the queue is flushed first,
        otherwise queued futures fail fast."""
        with self._wake:
            self._stop = True
            if not drain:
                for p in self._queue:
                    if not p.future.done():
                        p.future.set_exception(
                            BatcherStopped("batcher stopped before flush")
                        )
                self._queue.clear()
            self._wake.notify_all()
        self._thread.join(timeout=timeout)


# ---------------------------------------------------------------------------
# continuous (inflight) batching
# ---------------------------------------------------------------------------


def _bucket_grid(max_batch_size: int) -> Tuple[int, ...]:
    """Power-of-two flush sizes 1, 2, ... max_batch_size (mirrors
    ``engine.bucket_sizes`` so a flush size IS a compiled-program bucket —
    adaptive sizing never invents a new shape)."""
    sizes = []
    b = 1
    while b < max_batch_size:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch_size)
    return tuple(sizes)


class ContinuousBatcherStats:
    """Thread-safe accounting for the continuous flush loop.

    Alongside the MicroBatcher-compatible aggregates (``batches``,
    ``rows``, ``size_flushes``/``latency_flushes``) it tracks the signals
    the adaptive cap runs on: an EWMA of engine step time per flush
    bucket, and how often the cap (rather than the queue simply running
    dry) bounded a flush.
    """

    EWMA_ALPHA = 0.3

    def __init__(self):
        self._lock = named_lock("serve.batcher.stats")
        self.batches = 0
        self.rows = 0
        self.capped_flushes = 0   # the adaptive cap bounded the flush
        self.drain_flushes = 0    # the flush took the whole queue
        self._step_ms_ewma: Dict[int, float] = {}

    def record(self, rows: int, capped: bool):
        with self._lock:
            self.batches += 1
            self.rows += rows
            if capped:
                self.capped_flushes += 1
            else:
                self.drain_flushes += 1

    def record_step(self, bucket: int, step_ms: float):
        with self._lock:
            old = self._step_ms_ewma.get(bucket)
            self._step_ms_ewma[bucket] = (
                step_ms if old is None
                else self.EWMA_ALPHA * step_ms + (1 - self.EWMA_ALPHA) * old
            )

    def step_ms(self, bucket: int) -> Optional[float]:
        with self._lock:
            return self._step_ms_ewma.get(bucket)

    def step_ewma_ms(self) -> Dict[int, float]:
        with self._lock:
            return {b: round(v, 3) for b, v in self._step_ms_ewma.items()}

    def to_dict(self, max_batch_size: int) -> Dict[str, Any]:
        with self._lock:
            fill = (
                self.rows / (self.batches * max_batch_size)
                if self.batches
                else 0.0
            )
            return {
                "batches": self.batches,
                "rows": self.rows,
                "batch_fill_ratio": round(fill, 4),
                # MicroBatcher-compatible keys so ReplicaSet aggregation
                # works over mixed batcher kinds: a capped flush is the
                # size trigger's analogue; nothing here is timer-driven.
                "size_flushes": self.capped_flushes,
                "latency_flushes": 0,
                "drain_flushes": self.drain_flushes,
                "step_ms_ewma": {
                    str(b): round(v, 3)
                    for b, v in sorted(self._step_ms_ewma.items())
                },
            }


class ContinuousBatcher:
    """Inflight batcher: flush whatever is queued the moment the engine
    frees up, sized from queue depth and measured per-bucket step time.

    ``target_step_ms`` (optional) is the latency budget one flush may
    spend on the device: when a bucket's measured step-time EWMA exceeds
    it, the adaptive cap steps down the bucket grid — deep queues then
    drain in several smaller flushes whose *per-request* wait is bounded,
    instead of one giant flush that holds every rider for its full step.
    Unmeasured buckets are admitted optimistically (the first flush at a
    size is the measurement).

    The queue is bounded (``max_queue`` pending requests, enforced at
    submit AND by the deque's own maxlen — dmlint DML009): overload is
    refused at admission with :class:`QueueFull`, never absorbed into an
    unbounded backlog.
    """

    def __init__(
        self,
        infer_fn: Callable[[np.ndarray], np.ndarray],
        max_batch_size: int = 64,
        max_queue: int = 1024,
        target_step_ms: Optional[float] = None,
        name: str = "cbatcher",
    ):
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1: {max_batch_size}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1: {max_queue}")
        self.infer_fn = infer_fn
        self.max_batch_size = int(max_batch_size)
        self.max_queue = int(max_queue)
        self.target_step_ms = (
            float(target_step_ms) if target_step_ms else None
        )
        self._grid = _bucket_grid(self.max_batch_size)
        self.stats = ContinuousBatcherStats()
        self._queue: deque = deque(maxlen=self.max_queue)
        self._inflight = 0  # requests inside the current engine flush
        self._lock = named_lock("serve.batcher.queue")
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name=name, daemon=True
        )
        self._thread.start()

    # -- producer side -------------------------------------------------------

    def submit(self, x) -> Future:
        """Enqueue one request; raises :class:`QueueFull` past the bound."""
        x = np.asarray(x)
        fut: Future = Future()
        with self._wake:
            if self._stop:
                fut.set_exception(BatcherStopped("batcher is stopped"))
                return fut
            if len(self._queue) >= self.max_queue:
                # NB: the estimate must not re-take self._lock — the
                # condition already holds it (NamedLock is not reentrant).
                raise QueueFull(
                    len(self._queue), self.max_queue,
                    self._retry_estimate(len(self._queue) + self._inflight),
                )
            self._queue.append(_Pending(x, fut))
            self._wake.notify()
        return fut

    def _retry_estimate(self, depth: int) -> float:
        """Backlog-clearing estimate from the measured step time; lock-free
        (reads only the stats EWMA, which has its own lock)."""
        step = self.stats.step_ms(self._grid[-1])
        step_s = (step or 10.0) / 1000.0
        est = (depth / self.max_batch_size + 1.0) * step_s
        return min(max(est, 0.05), 5.0)

    def retry_after_s(self) -> float:
        """Rough time for the current backlog to clear: depth x measured
        step time / batch cap, clamped to a sane Retry-After range."""
        with self._lock:
            depth = len(self._queue) + self._inflight
        return self._retry_estimate(depth)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def pending(self) -> int:
        """Unanswered requests: queued AND inside the current flush.  The
        autoscaler/admission depth signal — a continuous batcher drains
        its queue into the in-flight batch immediately, so the queue
        alone under-reports load by up to one full flush."""
        with self._lock:
            return len(self._queue) + self._inflight

    def is_alive(self) -> bool:
        # dmlint: disable=unguarded-shared-state deliberate lock-free read: alive() sits on the per-request dispatch path and a single bool load is atomic under the GIL — staleness only delays failover by one round-robin pass
        return self._thread.is_alive() and not self._stop

    # -- adaptive cap --------------------------------------------------------

    def _cap_rows(self) -> int:
        """The most rows the next flush may take: the full batch cap,
        stepped down the bucket grid while the measured step time at the
        cap's bucket overruns ``target_step_ms``."""
        cap = self.max_batch_size
        if self.target_step_ms is None:
            return cap
        i = len(self._grid) - 1
        while i > 0:
            measured = self.stats.step_ms(self._grid[i])
            if measured is None or measured <= self.target_step_ms:
                break
            i -= 1
        return self._grid[i]

    def bucket_for(self, n: int) -> int:
        for b in self._grid:
            if b >= n:
                return b
        return self._grid[-1]

    # -- worker side ---------------------------------------------------------

    def _take_batch(self) -> Optional[List[_Pending]]:
        """Block until work exists (or stop); drain immediately up to the
        adaptive cap — no flush timer, the engine going idle IS the
        trigger."""
        with self._wake:
            while True:
                if self._stop and not self._queue:
                    return None
                if self._queue:
                    cap = self._cap_rows()
                    batch: List[_Pending] = []
                    rows = 0
                    while self._queue:
                        nxt = self._queue[0]
                        n = nxt.x.shape[0]
                        # Whole requests only (same contract as the
                        # MicroBatcher: one future = one contiguous slice
                        # of ONE engine call); a lone over-cap request
                        # flushes alone and the engine chunks it.
                        if batch and rows + n > cap:
                            break
                        batch.append(self._queue.popleft())
                        rows += n
                    self._inflight = len(batch)
                    self.stats.record(rows, capped=bool(self._queue))
                    return batch
                self._wake.wait(timeout=0.1)

    def _loop(self):
        from distributed_machine_learning_tpu.utils.heartbeat import (
            touch_heartbeat,
        )

        while True:
            batch = self._take_batch()
            if batch is None:
                return
            rows = sum(p.x.shape[0] for p in batch)
            try:
                xs = np.concatenate([p.x for p in batch], axis=0)
                t0 = time.monotonic()
                with obs.span(
                    "batch.flush",
                    {"rows": rows, "requests": len(batch)},
                    parent=batch[0].obs_ctx,
                ):
                    preds = np.asarray(self.infer_fn(xs))
                bucket = self.bucket_for(rows)
                step_ms = (time.monotonic() - t0) * 1000.0
                self.stats.record_step(bucket, step_ms)
                # The same per-bucket step measurement the adaptive cap
                # EWMA runs on also feeds the step-stream anomaly
                # detector (perf/anomaly.py): a sustained engine.step
                # outlier — a hung call, a degraded replica — becomes a
                # counter + flight dump naming this batcher instead of a
                # silently drifting p99.
                get_step_anomalies().observe(
                    f"serve.step.b{bucket}", step_ms / 1000.0,
                    who=self._thread.name,
                )
                off = 0
                for p in batch:
                    n = p.x.shape[0]
                    p.future.set_result(preds[off: off + n])
                    off += n
            except BaseException as exc:  # noqa: BLE001 - fail the batch only
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(exc)
            finally:
                with self._lock:
                    self._inflight = 0
            touch_heartbeat()

    def stop(self, drain: bool = True, timeout: float = 5.0):
        """Stop the worker; with ``drain`` the queue is flushed first,
        otherwise queued futures fail fast (``BatcherStopped`` — the
        redispatch signal)."""
        with self._wake:
            self._stop = True
            if not drain:
                for p in self._queue:
                    if not p.future.done():
                        p.future.set_exception(
                            BatcherStopped("batcher stopped before flush")
                        )
                self._queue.clear()
            self._wake.notify_all()
        self._thread.join(timeout=timeout)

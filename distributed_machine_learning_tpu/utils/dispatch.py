"""Optional cross-thread device-dispatch serialization.

Concurrent-trial executors (``ThreadTrialExecutor``) run many trials as
Python threads inside one process; each trial fires its own device
calls (init, per-epoch train program, eval, checkpoint readback).  XLA
serializes execution on the device and the runtime is thread-safe, so by
default nothing here does anything.

``dispatch_lock()`` returns a context manager that serializes the
device-call sections of concurrent trials when serialization is on, and
is a no-op otherwise:

- ``DML_SERIALIZE_DISPATCH=1`` turns it on;
- unset (or ``=0``) it is off.

Serialization costs thread-level device overlap — little on one chip,
which runs one program at a time — and keeps host-side work (scheduler
bookkeeping, checkpoint serialization, data prep) fully concurrent.
Whether the lock earns its keep is ROADMAP.md C3's question.

The reference stack has no analogue: Ray actors are processes, so its
trials never share a CUDA context from threads
(ray-tune-hpo-regression.py:469-480 relies on actor isolation).
"""

from __future__ import annotations

import contextlib
import os
import threading

from distributed_machine_learning_tpu.analysis.locks import named_lock
# Named + reentrant: participates in the lock-order graph
# (analysis/locks.py) under the role "dispatch".
_LOCK = named_lock("dispatch", reentrant=True)
_resolved: bool | None = None


def _serialize_on() -> bool:
    global _resolved
    if _resolved is None:
        flag = os.environ.get("DML_SERIALIZE_DISPATCH", "").strip()
        _resolved = flag in ("1", "true", "on")
    return _resolved


def _reset_for_tests() -> None:
    global _resolved
    _resolved = None


def serialization_on() -> bool:
    """Whether dispatch serialization is active for this process.

    The resolution is captured at FIRST use (then cached for the process
    lifetime): set ``DML_SERIALIZE_DISPATCH`` before the first trial
    runs, not mid-run.
    """
    return _serialize_on()


def dispatch_lock():
    """Context manager guarding a device-call section of a trial.

    Reentrant (RLock): a guarded section may call helpers that guard
    themselves. No-op unless serialization resolved on (see module doc;
    resolution is captured at first use — ``serialization_on``).
    """
    if _serialize_on():
        return _LOCK
    return contextlib.nullcontext()

"""Structured tracing: monotonic spans, thread-local stacks, and trace
context that crosses every process boundary in the stack.

``obs.span(name, attrs)`` is the one instrumentation primitive.  Enabled
(a :class:`Tracer` installed via :func:`configure`), it opens a span on
the calling thread's stack; on exit the completed span is appended to the
process's trace buffer AND to a per-process JSON-lines file under
``trace_dir`` (crash-tolerant: every landed span survives the process).
The driver merges the per-process files into one Chrome-trace JSON at
experiment end (``obs/export.py``; ``dml-tpu trace export``).

Two sinks, one primitive.  A span also lands in the ``jax.profiler``
trace's host plane as ``dml:<name>`` (a ``TraceAnnotation`` carrying the
span's scalar attrs) whenever a profiler session is running — the device
trace's clock, so an idle gap on the device can be put down to the span
the host was in.  No switch: "on" means someone called
``jax.profiler.start_trace``.  jax is never imported from here; the
annotation class is picked up from ``sys.modules`` once jax is there.

Disabled (no tracer, no profiler session: the default), ``span`` costs
two global reads, an ``is_enabled()`` on the profiler and returns a
singleton no-op context manager — no allocation, a few hundred ns, cheap
enough to leave at every epoch/request/chunk boundary
(tests/test_obs_plane.py pins this with an allocation + latency guard).

Cross-boundary context: a span's identity is ``(trace_id, span_id)``.
The driver threads it through the existing frame protocols — the process
executor's init frame, the cluster dispatch frame, the serve batcher's
pending entries — and the far side either installs it as the process
default (:func:`set_process_context`: new root spans adopt it as parent)
or passes it explicitly (``span(..., parent=ctx)``).  Wall-clock span
timestamps + monotonic durations make per-process files mergeable on one
timeline while keeping durations NTP-step-proof.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.obs.registry import get_registry


class _NoopSpan:
    """Singleton returned on the disabled path: zero state, zero writes."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key: str, value) -> "_NoopSpan":
        return self

    def end(self) -> None:
        return None

    @property
    def context(self) -> None:
        return None


_NOOP = _NoopSpan()

# -- the profiler's host plane --------------------------------------------------

PLANE_PREFIX = "dml:"
_PLANE_SCALARS = (str, int, float)  # bool is an int
# An annotation travels as ``name#k=v,k=v#``: these would end a value early.
_PLANE_UNSAFE = str.maketrans({"#": "_", ",": "_"})

_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def _find_annotation():
    """``jax.profiler.TraceAnnotation`` if the process has imported jax,
    else None — looked up, never imported, so obs stays stdlib-only."""
    global _annotation
    profiler = sys.modules.get("jax.profiler")
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _plane_value(value):
    return value.translate(_PLANE_UNSAFE) if isinstance(value, str) else value


class Span:
    """One live span.  Use as a context manager or call :meth:`end`.

    ``_tracer`` is None when only a profiler session is listening: the
    span then has no ids and lands in the host plane alone."""

    __slots__ = (
        "name", "attrs", "trace_id", "span_id", "parent_id",
        "_t0_mono", "_t0_wall", "_tracer", "_stacked", "_ended", "_ann",
    )

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 attrs: Optional[Dict[str, Any]],
                 trace_id: str, span_id: str, parent_id: Optional[str],
                 stacked: bool):
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self._tracer = tracer
        self._stacked = stacked
        self._ended = False
        self._ann = None
        self._t0_mono = time.monotonic()
        self._t0_wall = time.time()

    def _annotate(self, annotation) -> "Span":
        """Open this span in the running profiler session's host plane."""
        meta = {
            k: _plane_value(v) for k, v in self.attrs.items()
            if isinstance(v, _PLANE_SCALARS)
        }
        if self.span_id:
            meta["span_id"] = self.span_id
            if self.parent_id:
                meta["parent_id"] = self.parent_id
        self._ann = annotation(PLANE_PREFIX + self.name, **meta)
        return self

    def set(self, key: str, value) -> "Span":
        self.attrs[key] = value
        if self._ann is not None and isinstance(value, _PLANE_SCALARS):
            self._ann.set_metadata(**{key: _plane_value(value)})
        return self

    @property
    def context(self) -> Optional[Tuple[str, str]]:
        """``(trace_id, span_id)`` — hand this across a queue/frame and
        open the far side's span with ``parent=context``."""
        if self._tracer is None:
            return None
        return (self.trace_id, self.span_id)

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._tracer is not None:
            self._tracer._finish(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None and "error" not in self.attrs:
            self.set("error", exc_type.__name__)
        self.end()
        return False


class Tracer:
    """Per-process span collector with an optional JSONL file sink."""

    def __init__(self, trace_dir: Optional[str] = None, label: str = "proc",
                 trace_id: Optional[str] = None,
                 parent_span_id: Optional[str] = None,
                 buffer_limit: int = 100_000):
        self.label = label
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.default_parent = parent_span_id
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # tid -> that thread's live stack: lets a dump thread report every
        # thread's CURRENT open spans (the "hang site" in a stall dump).
        self._stacks: Dict[int, List[Span]] = {}
        self._lock = named_lock("obs.tracer")
        self._records: List[Dict[str, Any]] = []
        self._buffer_limit = int(buffer_limit)
        self._dropped = 0
        self._file = None
        self.path = None
        if trace_dir:
            try:
                os.makedirs(trace_dir, exist_ok=True)
                self.path = os.path.join(
                    trace_dir, f"trace_{label}_{os.getpid()}.jsonl"
                )
                self._file = open(self.path, "a", buffering=1)
            except OSError:
                get_registry().add("export_failures")
                self.path = None

    # -- span lifecycle ------------------------------------------------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
            with self._lock:
                self._stacks[threading.get_ident()] = stack
        return stack

    def start(self, name: str, attrs: Optional[Dict[str, Any]] = None,
              parent: Optional[Tuple[str, str]] = None) -> Span:
        stack = self._stack()
        if parent is not None:
            trace_id, parent_id = parent
        elif stack:
            trace_id, parent_id = stack[-1].trace_id, stack[-1].span_id
        else:
            trace_id, parent_id = self.trace_id, self.default_parent
        span = Span(self, name, attrs, trace_id, self._new_id(), parent_id,
                    stacked=True)
        stack.append(span)
        return span

    def start_detached(self, name: str,
                       attrs: Optional[Dict[str, Any]] = None,
                       parent: Optional[Tuple[str, str]] = None) -> Span:
        """A span that does NOT join the caller's thread stack — for
        driver-side activities (a trial's dispatch window) that begin and
        end on different event-loop iterations."""
        if parent is not None:
            trace_id, parent_id = parent
        else:
            trace_id, parent_id = self.trace_id, self.default_parent
        return Span(self, name, attrs, trace_id, self._new_id(), parent_id,
                    stacked=False)

    def _new_id(self) -> str:
        # "-", not ".": the profiler's trace reads a stat that parses as a
        # number as one, and "1673.10" would come back as 1673.1.
        return f"{os.getpid():x}-{next(self._ids):x}"

    def _finish(self, span: Span) -> None:
        if span._stacked:
            stack = self._stack()
            # Tolerate out-of-order ends (a leaked child span): remove by
            # identity wherever it sits.
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] is span:
                    del stack[i]
                    break
        self.add_record({
            "name": span.name,
            "ph": "X",
            "ts": round(span._t0_wall * 1e6, 1),
            "dur": round((time.monotonic() - span._t0_mono) * 1e6, 1),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {
                **span.attrs,
                "trace_id": span.trace_id,
                "span_id": span.span_id,
                **({"parent_id": span.parent_id} if span.parent_id else {}),
            },
        })

    def add_complete(self, name: str, dur_s: float,
                     attrs: Optional[Dict[str, Any]] = None,
                     end_wall: Optional[float] = None) -> None:
        """Record an already-measured interval (e.g. a jax compile event,
        whose duration arrives via a monitoring listener)."""
        end = end_wall if end_wall is not None else time.time()
        self.add_record({
            "name": name,
            "ph": "X",
            "ts": round((end - dur_s) * 1e6, 1),
            "dur": round(dur_s * 1e6, 1),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {**(attrs or {}), "trace_id": self.trace_id},
        })

    def add_record(self, record: Dict[str, Any]) -> None:
        get_registry().add("spans_recorded")
        with self._lock:
            if len(self._records) < self._buffer_limit:
                self._records.append(record)
            else:
                self._dropped += 1
            f = self._file
        if f is not None:
            try:
                f.write(json.dumps(record, default=str) + "\n")
            except (OSError, ValueError):
                get_registry().add("export_failures")

    # -- queries / teardown --------------------------------------------------

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def span_stacks(self) -> Dict[str, List[Dict[str, Any]]]:
        """Every thread's currently-open spans, outermost first — the
        flight recorder embeds this in dumps so a stall names its site."""
        with self._lock:
            stacks = {tid: list(stack) for tid, stack in self._stacks.items()}
        now = time.monotonic()
        return {
            str(tid): [
                {
                    "name": s.name,
                    "age_s": round(now - s._t0_mono, 3),
                    "attrs": dict(s.attrs),
                    "span_id": s.span_id,
                    "trace_id": s.trace_id,
                }
                for s in stack
            ]
            for tid, stack in stacks.items()
            if stack
        }

    def flush(self) -> None:
        with self._lock:
            f = self._file
        if f is not None:
            try:
                f.flush()
            except OSError:
                get_registry().add("export_failures")

    def close(self) -> None:
        with self._lock:
            f, self._file = self._file, None
        if f is not None:
            try:
                f.close()
            except OSError:
                get_registry().add("export_failures")


# -- process-wide installation -------------------------------------------------

_tracer: Optional[Tracer] = None


def install_tracer(tracer: Optional[Tracer]) -> None:
    global _tracer
    old, _tracer = _tracer, tracer
    if old is not None and old is not tracer:
        old.close()


def get_tracer() -> Optional[Tracer]:
    return _tracer


def tracing_enabled() -> bool:
    return _tracer is not None


def span(name: str, attrs: Optional[Dict[str, Any]] = None,
         parent: Optional[Tuple[str, str]] = None):
    """THE instrumentation call.  With no tracer installed and no
    ``jax.profiler`` session running: two global reads, one
    ``is_enabled()``, and a shared no-op object back — nothing allocated
    (the perf guard in tests/test_obs_plane.py holds this to a
    microsecond or so a call)."""
    t = _tracer
    annotation = _annotation or _find_annotation()
    on_plane = annotation is not None and annotation.is_enabled()
    if t is None:
        if not on_plane:
            return _NOOP
        return Span(None, name, attrs, None, None, None,
                    stacked=False)._annotate(annotation)
    live = t.start(name, attrs, parent)
    return live._annotate(annotation) if on_plane else live


def detached_span(name: str, attrs: Optional[Dict[str, Any]] = None,
                  parent: Optional[Tuple[str, str]] = None):
    t = _tracer
    if t is None:
        return _NOOP
    return t.start_detached(name, attrs, parent)


def add_complete(name: str, dur_s: float,
                 attrs: Optional[Dict[str, Any]] = None) -> None:
    t = _tracer
    if t is not None:
        t.add_complete(name, dur_s, attrs)


def current_context() -> Optional[Tuple[str, str]]:
    """The calling thread's innermost span context (None when disabled or
    no span is open) — attach it to queued work so the far side's spans
    parent correctly."""
    t = _tracer
    if t is None:
        return None
    stack = getattr(t._tls, "stack", None)
    if stack:
        return stack[-1].context
    if t.default_parent:
        return (t.trace_id, t.default_parent)
    return None


def set_process_context(trace_id: Optional[str],
                        parent_span_id: Optional[str]) -> None:
    """Adopt a remote parent as this process's default span parent (child
    processes / cluster workers call this with the dispatch frame's
    context)."""
    t = _tracer
    if t is not None:
        if trace_id:
            t.trace_id = trace_id
        t.default_parent = parent_span_id


def active_span_stacks() -> Dict[str, List[Dict[str, Any]]]:
    t = _tracer
    return t.span_stacks() if t is not None else {}


def disabled_path_overhead(iters: int = 100_000) -> Dict[str, float]:
    """Measure the tracing-DISABLED ``span()`` path (no tracer; and, where
    the caller has imported jax, the profiler bridge with no session
    running): ns per call and net allocated blocks across ``iters`` spans
    (must be ~0 — the disabled path returns a shared singleton and
    allocates nothing).

    This is the contract that makes always-on instrumentation acceptable
    in epoch/request/chunk hot paths.  Shared by the tier-1 perf guard
    (tests/test_obs_plane.py) and the CI gate (scripts/lint_gate.py with
    ``DML_OBS_PERF_GUARD=1``) so a regression gates the diff.  Any
    installed tracer is stashed and restored around the measurement.
    """
    import time as _time

    global _tracer
    stashed, _tracer = _tracer, None
    try:
        for _ in range(1000):  # warm the bytecode/caches
            with span("warm"):
                pass
        blocks0 = sys.getallocatedblocks()
        t0 = _time.perf_counter()
        for _ in range(iters):
            with span("guard"):
                pass
        elapsed = _time.perf_counter() - t0
        net_blocks = sys.getallocatedblocks() - blocks0
    finally:
        _tracer = stashed
    return {
        "ns_per_span": round(elapsed / iters * 1e9, 1),
        "net_blocks": net_blocks,
        "iters": iters,
    }

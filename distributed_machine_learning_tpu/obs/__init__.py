"""obs/ — the one observability plane (ISSUE 13 tentpole).

Three legs, one import:

* **Structured tracing** (``obs/trace.py``): ``obs.span(name, attrs)``
  context managers on monotonic clocks with thread-local span stacks and
  trace context that rides every existing frame protocol — driver ->
  process child (init frame), head -> cluster worker (dispatch frame),
  serve request -> replica -> batcher -> engine (pending entries).
  Per-process JSONL span files merge into Chrome-trace/Perfetto JSON
  (``obs/export.py``, ``dml-tpu trace``).  While a ``jax.profiler``
  session runs, every span also lands in its host plane as
  ``dml:<name>``, on the device trace's clock.
* **Always-on flight recorder** (``obs/flight.py``): a bounded,
  preallocated, lock-free ring of recent events per process, dumped
  automatically on watchdog expiry, STALLED transitions, lease expiry,
  breaker-open, SIGTERM, and bench probe wedges.
* **Unified MetricsRegistry** (``obs/registry.py``): the counter families
  that used to live in six private registries all register here; the
  cluster head aggregates worker snapshots into one place.

Everything is stdlib-only and safe to import anywhere (no jax at import
time); the disabled tracing path is two None-checks and one
``is_enabled()`` on the profiler.

See docs/observability.md for the span names, flight-recorder
triggers, and the counter -> registry migration map.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

from distributed_machine_learning_tpu.obs.flight import (
    FlightRecorder,
    dump_dir,
    dump_flight_recorder,
    get_flight_recorder,
    record_event,
    set_dump_dir,
)
from distributed_machine_learning_tpu.obs.registry import (
    MetricsRegistry,
    aggregate_scalars,
    get_registry,
)
from distributed_machine_learning_tpu.obs.trace import (
    Span,
    Tracer,
    active_span_stacks,
    add_complete,
    current_context,
    detached_span,
    disabled_path_overhead,
    get_tracer,
    install_tracer,
    set_process_context,
    span,
    tracing_enabled,
)
from distributed_machine_learning_tpu.obs.export import (
    chrome_trace,
    merge_trace_dir,
    read_trace_files,
    summarize_trace,
)

event = record_event  # ``obs.event("kind", {...})``: one flight-ring write

__all__ = [
    "FlightRecorder", "MetricsRegistry", "Span", "Tracer",
    "active_span_stacks", "add_complete", "aggregate_scalars",
    "chrome_trace", "configure", "configure_from_frame", "current_context",
    "detached_span", "disabled_path_overhead", "dump_dir",
    "dump_flight_recorder", "event",
    "flush", "get_flight_recorder", "get_registry", "get_tracer",
    "install_tracer", "merge_trace_dir",
    "read_trace_files", "record_event", "set_dump_dir",
    "set_process_context", "shutdown", "span", "summarize_trace",
    "trace_context_frame", "tracing_enabled",
]


def configure(
    trace_dir: Optional[str] = None,
    label: str = "proc",
    trace_id: Optional[str] = None,
    parent_span_id: Optional[str] = None,
    dump_dir: Optional[str] = None,
    flight_mirror: Optional[str] = None,
) -> None:
    """Install the process's telemetry plane.

    ``trace_dir`` enables tracing (spans stream to a per-process JSONL
    file there); None leaves tracing in its current state.  ``dump_dir``
    sets where automatic flight-recorder dumps land.  ``flight_mirror``
    turns on the crash-safe per-event mirror (probe children).
    """
    if trace_dir is not None:
        install_tracer(Tracer(
            trace_dir, label=label, trace_id=trace_id,
            parent_span_id=parent_span_id,
        ))
    elif trace_id is not None or parent_span_id is not None:
        set_process_context(trace_id, parent_span_id)
    if dump_dir is not None:
        set_dump_dir(dump_dir)
    if flight_mirror is not None:
        get_flight_recorder().set_mirror(flight_mirror)


def flush() -> None:
    """Flush the tracer's file sink (if any) — call at report/teardown
    boundaries so a killed process loses at most the in-flight span."""
    t = get_tracer()
    if t is not None:
        t.flush()


def shutdown() -> None:
    """Flush + close + uninstall the tracer (driver teardown after the
    merge).  The flight recorder and registry stay — they are process
    lifetime by design."""
    install_tracer(None)


def trace_context_frame(
    parent: Optional[Tuple[str, str]] = None,
) -> Optional[Dict[str, Any]]:
    """The dict a driver attaches to a dispatch/init frame so the far
    process can join this trace: ``{"trace_dir", "trace_id",
    "parent_span_id", "dump_dir"}``.  ``parent`` overrides the parent
    span (the driver's per-trial dispatch span).  None when nothing is
    configured — frames stay exactly as they were before obs existed.
    """
    t = get_tracer()
    dumps = dump_dir()
    if t is None and dumps is None:
        return None
    ctx: Dict[str, Any] = {}
    if dumps:
        ctx["dump_dir"] = dumps
    if t is not None:
        if parent is not None:
            trace_id, parent_id = parent
        else:
            trace_id, parent_id = t.trace_id, t.default_parent
        ctx.update({
            "trace_dir": os.path.dirname(t.path) if t.path else None,
            "trace_id": trace_id,
            "parent_span_id": parent_id,
        })
    return ctx


def configure_from_frame(ctx: Optional[Dict[str, Any]],
                         label: str = "child") -> None:
    """Child-process side of :func:`trace_context_frame`."""
    if not ctx:
        return
    configure(
        trace_dir=ctx.get("trace_dir"),
        label=label,
        trace_id=ctx.get("trace_id"),
        parent_span_id=ctx.get("parent_span_id"),
        dump_dir=ctx.get("dump_dir"),
    )

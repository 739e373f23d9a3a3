"""Persistent XLA compile-cache ownership + per-trial compile accounting.

Moved here from ``utils/compile_cache.py`` (which remains as a shim) when
the compile-artifact layer grew into a package.  Two mechanisms:

1. :func:`enable_persistent_cache` — turns on JAX's on-disk compilation
   cache so that a trial whose traced program matches ANY earlier trial
   (this run or a previous one, this process or another) skips XLA backend
   compilation entirely.  Every driver calls this at startup; it is not
   left to the user.

2. :class:`CompileTimeTracker` — a process-wide listener on JAX's
   monitoring events that attributes compile seconds, backend-compile
   EVENT counts, and persistent-cache hits to the thread that triggered
   them.  Trial threads each jit their own programs, so per-thread
   attribution IS per-trial attribution.  The event COUNTS (not just
   seconds) are what the compile-once acceptance checks assert: "a fresh
   process with a populated cache records 0 new backend compiles" is
   ``total_backend_compiles() == 0``, not an eyeballed duration.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional
from distributed_machine_learning_tpu.analysis.locks import named_lock

# Everything this package builds at run time (XLA cache, AOT executables,
# the native .so) lives under ONE fixed, git-ignored directory inside the
# checkout: the path is part of a cache entry's key, so a directory that
# moves (a home or temp dir that is new on every machine) never hits.
_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".dml_cache",
)
_DEFAULT_DIR = os.path.join(_CACHE_ROOT, "xla")

_lock = named_lock("compilecache.tracker.registry")
_enabled_dir: Optional[str] = None

# Monitoring event names (`/jax/core/compile/*`,
# `/jax/compilation_cache/*`) — verified against this image's jax.
_DURATION_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def cache_root() -> str:
    """The in-checkout directory run-time build products go under."""
    return _CACHE_ROOT


def resolve_cache_dir(cache_dir: Optional[str] = None) -> str:
    """Where the persistent cache goes: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (nothing outranks it), else ``cache_dir``, else the fixed
    in-checkout default."""
    return os.path.expanduser(
        os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir or _DEFAULT_DIR
    )


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`resolve_cache_dir` and drop the min-size/min-time thresholds so
    even small HPO programs are cached.  Idempotent; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets no directory in code.
    """
    global _enabled_dir
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    resolved = resolve_cache_dir(cache_dir)
    with _lock:
        if _enabled_dir == resolved:
            return resolved
        os.makedirs(resolved, exist_ok=True)
        import jax

        if not from_env:
            jax.config.update("jax_compilation_cache_dir", resolved)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # By default jax also turns on XLA's GPU autotune cache, whose
        # directory PATH lands in compile_options — which is hashed into
        # the cache key, so two hosts with different cache dirs compute
        # DIFFERENT keys for the same program and artifacts shipped
        # between them (cluster origin, bench children) can never hit.
        # Disable it: key stability across hosts is the whole point, and
        # the knob only affects a GPU autotuning sidecar cache.
        jax.config.update("jax_persistent_cache_enable_xla_caches", "")
        if _enabled_dir is not None and _enabled_dir != resolved:
            # JAX instantiates the cache object lazily ONCE; re-pointing the
            # config after that is silently ignored without a reset.
            from jax.experimental.compilation_cache import compilation_cache

            compilation_cache.reset_cache()
        _enabled_dir = resolved
    return resolved


def cache_dir() -> Optional[str]:
    """The directory the persistent cache is enabled at (None if not)."""
    return _enabled_dir


def cache_entry_count() -> int:
    """Number of compiled executables currently in the persistent cache."""
    if not _enabled_dir or not os.path.isdir(_enabled_dir):
        return 0
    return sum(1 for name in os.listdir(_enabled_dir) if name.endswith("-cache"))


class CompileTimeTracker:
    """Attributes JAX compile seconds + persistent-cache hits per thread.

    JAX runs monitoring listeners inline on the thread that compiles, so
    ``threading.get_ident()`` inside the listener identifies which trial
    thread paid for a compilation.  A single process-wide instance is
    installed lazily (:func:`get_tracker`); the executor snapshots a thread's
    counters before a trial starts and diffs after each report.
    """

    def __init__(self):
        self._lock = named_lock("compilecache.tracker")
        self._seconds: Dict[int, float] = {}
        self._hits: Dict[int, int] = {}
        self._backend_seconds: Dict[int, float] = {}
        self._backend_count: Dict[int, int] = {}
        self._trace_count: Dict[int, int] = {}
        self._max_backend_s: float = 0.0

    # -- listener callbacks (run on the compiling thread) -------------------

    def _on_duration(self, event: str, duration: float, **_kw):
        if event not in _DURATION_EVENTS:
            return
        ident = threading.get_ident()
        with self._lock:
            self._seconds[ident] = self._seconds.get(ident, 0.0) + duration
            if event == _DURATION_EVENTS[0]:
                self._backend_seconds[ident] = (
                    self._backend_seconds.get(ident, 0.0) + duration
                )
                self._backend_count[ident] = (
                    self._backend_count.get(ident, 0) + 1
                )
                self._max_backend_s = max(self._max_backend_s, duration)
            elif event == _DURATION_EVENTS[1]:
                self._trace_count[ident] = self._trace_count.get(ident, 0) + 1
        if event == _DURATION_EVENTS[0]:
            # Into the observability plane: each backend compile becomes a
            # trace span (the listener hands us the measured duration, so
            # the span is recorded retroactively) and a flight-ring event —
            # a wedged process's dump shows what was compiling when.
            from distributed_machine_learning_tpu import obs

            obs.add_complete("compile.backend", duration)
            obs.event("backend_compile", {"dur_s": round(duration, 4)})

    def _on_event(self, event: str, **_kw):
        if event != _CACHE_HIT_EVENT:
            return
        ident = threading.get_ident()
        with self._lock:
            self._hits[ident] = self._hits.get(ident, 0) + 1

    # -- queries ------------------------------------------------------------

    def thread_seconds(self, ident: Optional[int] = None) -> float:
        """Cumulative compile seconds (trace + lower + backend) on a thread."""
        ident = ident if ident is not None else threading.get_ident()
        with self._lock:
            return self._seconds.get(ident, 0.0)

    def thread_backend_seconds(self, ident: Optional[int] = None) -> float:
        """Cumulative XLA backend-compile seconds on a thread (the part a
        persistent-cache hit eliminates)."""
        ident = ident if ident is not None else threading.get_ident()
        with self._lock:
            return self._backend_seconds.get(ident, 0.0)

    def thread_cache_hits(self, ident: Optional[int] = None) -> int:
        ident = ident if ident is not None else threading.get_ident()
        with self._lock:
            return self._hits.get(ident, 0)

    def total_seconds(self) -> float:
        with self._lock:
            return sum(self._seconds.values())

    def total_cache_hits(self) -> int:
        with self._lock:
            return sum(self._hits.values())

    def total_backend_compiles(self) -> int:
        """Backend-compile EVENTS in this process.  NOTE: on this jax the
        event fires around the compile-or-fetch section, so persistent-
        cache HITS count too — :meth:`total_uncached_compiles` is the
        number of compiles that actually ran the XLA compiler."""
        with self._lock:
            return sum(self._backend_count.values())

    def total_uncached_compiles(self) -> int:
        """Backend compiles NOT served by the persistent cache — the
        number every cache layer exists to hold at the distinct-program
        count, and at ZERO for a warm restart (the compile-once
        acceptance checks assert on exactly this)."""
        with self._lock:
            return max(
                sum(self._backend_count.values()) - sum(self._hits.values()),
                0,
            )

    def total_traces(self) -> int:
        """Jaxpr traces in this process.  The import-time guard asserts this
        stays flat across an import sweep — tracing at import is hidden
        startup cost every process pays before doing any work."""
        with self._lock:
            return sum(self._trace_count.values())

    def max_backend_compile_s(self) -> float:
        """Longest single XLA backend compile seen in this process — the
        pessimistic price of compiling a program no cache has seen."""
        with self._lock:
            return self._max_backend_s

    def snapshot(self) -> Dict[str, float]:
        """Process totals for the ``compile`` state block (driver-scoped via
        delta, same discipline as ``ckpt.metrics``)."""
        with self._lock:
            backend = sum(self._backend_count.values())
            hits = sum(self._hits.values())
            return {
                "backend_compiles": backend,
                # Compiles the XLA compiler actually ran (the event above
                # also fires on persistent-cache hits): the compile-once
                # invariant is THIS staying at the distinct-program count.
                "backend_compiles_uncached": max(backend - hits, 0),
                "backend_compile_s": round(
                    sum(self._backend_seconds.values()), 4
                ),
                "compile_wall_s": round(sum(self._seconds.values()), 4),
                "persistent_cache_hits": hits,
                "traces": sum(self._trace_count.values()),
            }


_tracker: Optional[CompileTimeTracker] = None


def get_tracker() -> CompileTimeTracker:
    """The process-wide tracker, installing the JAX listeners on first use."""
    global _tracker
    with _lock:
        if _tracker is None:
            import jax.monitoring

            _tracker = CompileTimeTracker()
            jax.monitoring.register_event_duration_secs_listener(
                _tracker._on_duration
            )
            jax.monitoring.register_event_listener(_tracker._on_event)
    return _tracker

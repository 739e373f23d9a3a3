"""Ahead-of-time executable cache: compile once, load everywhere.

The persistent XLA cache (``tracker.enable_persistent_cache``) already
makes a repeated BACKEND compile free — but the repeat process still pays
tracing and lowering, and still has to reach the compile call at all.  This
layer goes one step further where the backend supports it:
``jax.jit(fn).lower(*args).compile()`` produces a loaded executable, and
``jax.experimental.serialize_executable`` round-trips it to bytes — so a
restarted serve replica, a pre-warmed trial runner, or a second bench child
deserializes the finished executable and skips trace/lower/compile
entirely.

Keying is :func:`compilecache.keys.program_key` — the same id the cluster
origin and the persistent-cache layer use, so every layer agrees on what
"the same program" means.

Trust model: the serialized payload embeds pytree defs, which ride pickle
(jax's own serialization format).  The store is therefore for
**framework-owned directories only** — the local AOT dir and artifacts
received over the (already pickled, optionally HMAC'd) cluster control
plane.  Checkpoint bytes never come near this path (test_import_guard
keeps the checkpoint formats pickle-free; this file is deliberately not in
that list because executables are process-trust artifacts, not data).

Failure posture: every load path degrades to a plain compile — a stale,
truncated, or cross-version payload must cost a recompile, never an error.
A deserialized executable is strict about argument dtypes/shapes; if a call
ever rejects its inputs the entry is dropped and the call re-dispatches
through ordinary ``jax.jit`` (counted, so drift is visible).
"""

from __future__ import annotations

import os
import pickle
import re
import tempfile
import threading
from typing import Any, Callable, Dict, Optional, Sequence

from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.compilecache.counters import get_counters
from distributed_machine_learning_tpu.compilecache import tracker as _tracker

_MAGIC = b"DMLAOT1\n"

# ``func.func public @main(%arg3: tensor<8x4xf32> {..., tf.aliasing_output
# = 1 : i32, ...})`` — the MLIR attribute jax's lowering stamps on every
# input buffer that will ALIAS an output (donation that actually took).
# ``jax.buffer_donor`` marks a donated input XLA may scavenge for
# intermediates even though no output matches its aval (the consumed-slab
# case — see data/pipeline.py's warning filter).
_ARG_RE = re.compile(r"%arg(\d+):")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DONOR_RE = re.compile(r"jax\.buffer_donor\s*=\s*true")


def lowered_alias_info(lowered) -> Dict[str, Any]:
    """Input/output aliasing of a ``jax.jit(...).lower(...)`` result,
    WITHOUT compiling it (the donation decision is made at lowering time;
    reading it must stay allocation- and compile-free — the jaxlint
    donation verifier's whole contract, analysis/jaxlint/donation.py).

    Returns ``{"num_args": N, "aliased": {arg_index: output_index},
    "buffer_donors": {arg_index, ...}}`` over the FLATTENED argument list
    (the order ``jax.tree_util.tree_leaves`` yields the example args in).
    """
    text = lowered.as_text()
    # Only the entry function's signature matters; stop at its body so a
    # nested func's %arg0 cannot shadow main's.
    main = text.split("func.func public @main", 1)
    sig = main[1].split("{\n", 1)[0] if len(main) == 2 else text
    # Per-arg attribute dicts may embed strings containing braces
    # (``mhlo.sharding = "{replicated}"``), so bracket matching is not an
    # option: scan each arg's span up to the next ``%argN:`` token (or
    # the result arrow) instead.
    aliased: Dict[int, int] = {}
    donors = set()
    num_args = 0
    matches = list(_ARG_RE.finditer(sig))
    for i, m in enumerate(matches):
        idx = int(m.group(1))
        num_args = max(num_args, idx + 1)
        end = matches[i + 1].start() if i + 1 < len(matches) else len(sig)
        span = sig[m.end():end]
        if i + 1 >= len(matches):
            span = span.split("->", 1)[0]
        am = _ALIAS_RE.search(span)
        if am:
            aliased[idx] = int(am.group(1))
        if _DONOR_RE.search(span):
            donors.add(idx)
    return {"num_args": num_args, "aliased": aliased,
            "buffer_donors": donors}


def _execution_devices(args, jit_kwargs):
    """The devices the program for ``args`` runs on, in assignment order.

    ``deserialize_and_load`` loads for EVERY device of the backend unless
    told otherwise, and an executable loaded that way rejects a
    one-device program's arguments on any multi-device host."""
    import jax
    from jax.sharding import NamedSharding, Sharding

    shardings = [
        s for s in jax.tree.leaves(
            (jit_kwargs or {}).get("in_shardings"),
            is_leaf=lambda s: isinstance(s, Sharding),
        ) if isinstance(s, Sharding)
    ] + [a.sharding for a in jax.tree.leaves(args)
         if isinstance(a, jax.Array)]
    for s in shardings:
        if isinstance(s, NamedSharding):
            return list(s.mesh.devices.flat)
    for s in shardings:
        return sorted(s.device_set, key=lambda d: d.id)
    return [jax.devices()[0]]


def default_aot_dir() -> str:
    """``<persistent cache dir>/aot`` — follows the same rule as the XLA
    cache (``tracker.resolve_cache_dir``)."""
    base = _tracker.cache_dir() or _tracker.resolve_cache_dir()
    return os.path.join(base, "aot")


class _Entry:
    __slots__ = ("compiled", "fallback", "make_fallback", "unproven")

    def __init__(self, compiled, unproven=False):
        self.compiled = compiled
        self.fallback = None
        self.make_fallback = None
        # Imported from disk and not yet run to completion once: XLA:CPU
        # can load a serialized program whose fusion symbols it then fails
        # to find, and says so only when the (async) result is awaited.
        self.unproven = unproven


class ExecutableCache:
    """Program-key -> loaded executable, with a serialized on-disk tier.

    ``get_or_compile(key, fn, *args)`` resolves in order:

    1. in-memory (``program_hits``);
    2. on-disk serialized executable ``<dir>/<key>.aotexec``
       (``aot_imports`` + ``program_hits``);
    3. compile via ``jax.jit(fn, ...).lower(*args).compile()``
       (``program_misses``), then export the serialized executable
       (``aot_exports``) — or mark the backend unsupported
       (``aot_unsupported``) and rely on the persistent XLA cache for the
       cross-process story.

    Programs with ``donate_argnums`` skip tier 2 in both directions (see
    ``get_or_compile``).  The returned callable accepts the same concrete
    arguments as ``fn``.
    """

    def __init__(self, directory: Optional[str] = None,
                 persist: bool = True):
        self._dir = directory or default_aot_dir()
        self._persist = persist
        self._lock = named_lock("compilecache.aot")
        self._mem: Dict[str, _Entry] = {}
        self._serialize_supported: Optional[bool] = None

    @property
    def directory(self) -> str:
        return self._dir

    # -- disk tier -----------------------------------------------------------

    def _path(self, key: str) -> str:
        return os.path.join(self._dir, f"{key}.aotexec")

    def _load_from_disk(self, key: str, execution_devices):
        path = self._path(key)
        if not self._persist or not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                magic = f.read(len(_MAGIC))
                if magic != _MAGIC:
                    return None
                payload, in_tree, out_tree = pickle.load(f)
            from jax.experimental import serialize_executable as se

            return se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=execution_devices,
            )
        except Exception:  # noqa: BLE001 - stale/cross-version payloads
            # A damaged entry must cost a recompile, never an error; drop
            # it so the fresh export below replaces it.
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def _export_to_disk(self, key: str, compiled) -> bool:
        if not self._persist:
            return False
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            os.makedirs(self._dir, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self._dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(_MAGIC)
                    pickle.dump((payload, in_tree, out_tree), f,
                                protocol=pickle.HIGHEST_PROTOCOL)
                os.replace(tmp, self._path(key))  # atomic: no torn entries
            finally:
                if os.path.exists(tmp):
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
            self._serialize_supported = True
            return True
        except Exception:  # noqa: BLE001 - backend without serialization
            self._serialize_supported = False
            return False

    # -- resolution ----------------------------------------------------------

    def get_or_compile(
        self,
        key: str,
        fn: Callable,
        *args,
        static_argnums: Sequence[int] = (),
        donate_argnums: Sequence[int] = (),
        jit_kwargs: Optional[Dict[str, Any]] = None,
    ) -> Callable:
        """Resolve ``key`` to a callable executable for ``fn(*args)``.

        ``args`` are example arguments of the exact shapes/dtypes the
        program will be called with (they are only traced/lowered on a
        miss, never executed).  ``jit_kwargs`` passes extra ``jax.jit``
        options through (``in_shardings``/``out_shardings`` for programs
        compiled under a named mesh) — they shape the executable, so the
        caller's ``key`` must already encode them
        (``keys.sharded_program_key``)."""
        counters = get_counters()
        with self._lock:
            entry = self._mem.get(key)
        if entry is not None:
            counters.add("program_hits")
            return self._wrap(key, entry)

        # A donating program never goes through the disk tier: an import is
        # only proven by its first call, and a refusal that comes after the
        # inputs were donated cannot be recovered.  The persistent XLA
        # cache still spares it the backend compile in the next process.
        on_disk = not donate_argnums
        compiled = self._load_from_disk(
            key, _execution_devices(args, jit_kwargs)
        ) if on_disk else None
        if compiled is not None:
            counters.add("program_hits")
            counters.add("aot_imports")
            self._capture_cost(key, compiled, from_disk=True)
            entry = self._remember(key, compiled, fn, static_argnums,
                                   donate_argnums, jit_kwargs,
                                   unproven=True)
            return self._wrap(key, entry)

        counters.add("program_misses")
        jitted = self._jit(fn, static_argnums, donate_argnums, jit_kwargs)
        compiled = jitted.lower(*args).compile()
        if on_disk:
            if self._export_to_disk(key, compiled):
                counters.add("aot_exports")
            else:
                counters.add("aot_unsupported")
        self._capture_cost(key, compiled, from_disk=False)
        entry = self._remember(key, compiled, fn, static_argnums,
                               donate_argnums, jit_kwargs)
        return self._wrap(key, entry)

    def _capture_cost(self, key: str, compiled, from_disk: bool) -> None:
        """Cost-model audit capture (perf/costmodel.py) — riding ONLY on
        executables this cache was compiling or deserializing anyway, so
        the audit adds zero compiles by construction.  A disk hit prefers
        the sidecar written at export time (it carries the ORIGIN
        process's numbers across workers); the fallback reads the
        deserialized executable's own analysis.  Never raises: cost
        capture is telemetry, not a cache dependency."""
        try:
            from distributed_machine_learning_tpu.perf import costmodel

            if from_disk and self._persist and costmodel.load_program_cost(
                key, self._dir
            ) is not None:
                return
            costmodel.record_program_cost(
                key, compiled, self._dir if self._persist else None
            )
        except Exception:  # noqa: BLE001 - audit must never cost a trial
            pass

    @staticmethod
    def _jit(fn, static_argnums, donate_argnums, jit_kwargs=None):
        import jax

        kwargs = dict(jit_kwargs or {})
        if static_argnums:
            kwargs["static_argnums"] = tuple(static_argnums)
        if donate_argnums:
            kwargs["donate_argnums"] = tuple(donate_argnums)
        return jax.jit(fn, **kwargs)

    def _remember(self, key, compiled, fn, static_argnums, donate_argnums,
                  jit_kwargs=None, unproven=False):
        # The fallback is built lazily: a plain jit of the original fn, used
        # only if the AOT executable ever rejects its arguments (dtype /
        # weak-type drift between the exporting and importing process).
        entry = _Entry(compiled, unproven)

        def fallback(*call_args):
            if entry.fallback is None:
                entry.fallback = self._jit(fn, static_argnums,
                                           donate_argnums, jit_kwargs)
            return entry.fallback(*call_args)

        entry.make_fallback = fallback
        with self._lock:
            self._mem[key] = entry
        return entry

    def _wrap(self, key: str, entry: _Entry) -> Callable:
        def call(*args):
            import jax

            try:
                out = entry.compiled(*args)
                if entry.unproven:
                    jax.block_until_ready(out)
                    entry.unproven = False
                return out
            except (TypeError, ValueError, jax.errors.JaxRuntimeError):
                # Strict AOT signature mismatch, or an imported executable
                # the runtime refuses at its first call (XLA:CPU reports
                # fusion symbols of a deserialized program "not found"):
                # drop the entry and serve through ordinary jit (persistent
                # cache still applies).
                get_counters().add("aot_unsupported")
                with self._lock:
                    self._mem.pop(key, None)
                try:
                    os.unlink(self._path(key))
                except OSError:
                    pass
                return entry.make_fallback(*args)

        return call

    # -- introspection ---------------------------------------------------------

    def __contains__(self, key: str) -> bool:
        with self._lock:
            if key in self._mem:
                return True
        return self._persist and os.path.exists(self._path(key))

    def mem_size(self) -> int:
        with self._lock:
            return len(self._mem)

    def disk_keys(self) -> Sequence[str]:
        if not self._persist or not os.path.isdir(self._dir):
            return []
        return sorted(
            n[: -len(".aotexec")]
            for n in os.listdir(self._dir)
            if n.endswith(".aotexec")
        )

    def stats(self) -> Dict[str, Any]:
        return {
            "mem_programs": self.mem_size(),
            "disk_programs": len(self.disk_keys()),
            "directory": self._dir,
            "serialize_supported": self._serialize_supported,
        }

"""Pluggable byte storage for checkpoints and experiment artifacts.

The reference keeps results on a local ``local_dir`` only
(`/root/reference/ray-tune-hpo-regression.py:476`); a TPU pod needs shared
storage — checkpoints written by one host must be restorable on another
(PBT exploit across workers, preemption recovery), and the BASELINE north
star names GCS explicitly.  This module dispatches on the path scheme:

* plain paths / ``file://``  -> ``LocalStorage`` (atomic POSIX writes)
* ``gs://``, ``s3://``, ...  -> ``FsspecStorage`` (via fsspec/gcsfs when
  installed; a clear error otherwise — the libraries are optional)
* ``mem://``                 -> ``MemoryStorage`` (process-local fake for
  tests; no disk, no network)

Every consumer (checkpoint save/load, retention pruning) goes through
``get_storage`` so a ``storage_path='gs://bucket/exp'`` flows end to end
without any caller branching on scheme.

Failure hardening (chaos.py is the harness that proves it):

* ``get_storage`` composes two wrappers around the scheme backend:
  an optional **fault wrapper** (installed by ``chaos.activate`` — injects
  deterministic, seeded IOErrors/corruption/latency for tests) and a
  **retry wrapper** (``RetryingStorage``: exponential backoff + jitter +
  a bounded attempt budget for transient I/O faults — shared storage on a
  pod is exactly the place writes flake).  Order matters: retries sit
  OUTSIDE the fault layer so an injected transient error is absorbed the
  same way a real one would be.
* ``retry_call`` is the same policy as a bare function, used by the
  experiment store's local JSON writes (state snapshots, params) which
  bypass the byte-backend interface.
"""

from __future__ import annotations

import hashlib
import os
import posixpath
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple
from distributed_machine_learning_tpu.analysis.locks import named_lock


class StorageBackend:
    """Minimal byte-level interface checkpoints need."""

    def write_bytes(self, path: str, data: bytes) -> str:
        raise NotImplementedError

    def write_chunks(self, path: str, chunks: Iterable[bytes]) -> str:
        """Write the concatenation of ``chunks`` (bytes-like objects) to
        ``path`` — how a payload too large to build in memory reaches
        storage.  A chunk may be a view of memory its producer reuses:
        consume it before asking for the next.  ``chunks`` is iterated once
        per attempt, from the start (the retry wrapper iterates again), so
        a caller that may be retried passes a re-iterable, not a one-shot
        generator.  This default joins them and calls ``write_bytes``, so a
        backend that only knows whole payloads behaves as it always did."""
        return self.write_bytes(path, b"".join(chunks))

    def read_bytes(self, path: str) -> Optional[bytes]:
        raise NotImplementedError

    def exists(self, path: str) -> bool:
        raise NotImplementedError

    def listdir(self, path: str) -> List[str]:
        """Names (not full paths) of entries under ``path``; [] if absent."""
        raise NotImplementedError

    def delete(self, path: str) -> None:
        raise NotImplementedError

    def join(self, *parts: str) -> str:
        return posixpath.join(*parts)


class LocalStorage(StorageBackend):
    """Local filesystem with atomic writes (temp file + rename)."""

    def write_bytes(self, path: str, data: bytes) -> str:
        return self.write_chunks(path, (data,))

    def write_chunks(self, path: str, chunks: Iterable[bytes]) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                for chunk in chunks:
                    f.write(chunk)
            os.replace(tmp, path)  # atomic on POSIX
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return path

    def read_bytes(self, path: str) -> Optional[bytes]:
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    def exists(self, path: str) -> bool:
        return os.path.exists(path)

    def listdir(self, path: str) -> List[str]:
        if not os.path.isdir(path):
            return []
        return sorted(os.listdir(path))

    def delete(self, path: str) -> None:
        if os.path.exists(path):
            os.unlink(path)

    def join(self, *parts: str) -> str:
        return os.path.join(*parts)


class MemoryStorage(StorageBackend):
    """Process-local in-memory store keyed by full path (test fake).

    A single shared namespace (class-level) so independently constructed
    instances — e.g. the saver inside the executor and the loader in a test —
    see the same data, mirroring how a bucket behaves across components.
    """

    _store: Dict[str, bytes] = {}
    _lock = named_lock("tune.storage.mem")

    def write_bytes(self, path: str, data: bytes) -> str:
        with self._lock:
            self._store[path] = bytes(data)
        return path

    def read_bytes(self, path: str) -> Optional[bytes]:
        with self._lock:
            return self._store.get(path)

    def exists(self, path: str) -> bool:
        with self._lock:
            return path in self._store

    def listdir(self, path: str) -> List[str]:
        prefix = path.rstrip("/") + "/"
        with self._lock:
            names = {
                key[len(prefix):].split("/", 1)[0]
                for key in self._store if key.startswith(prefix)
            }
        return sorted(names)

    def delete(self, path: str) -> None:
        with self._lock:
            self._store.pop(path, None)

    @classmethod
    def clear(cls) -> None:
        with cls._lock:
            cls._store.clear()


class FsspecStorage(StorageBackend):
    """Remote object storage (gs://, s3://, ...) through fsspec."""

    def __init__(self, scheme: str):
        try:
            import fsspec
        except ImportError as e:  # pragma: no cover - env-dependent
            raise ImportError(
                f"storage scheme {scheme!r} needs the optional 'fsspec' "
                f"package (plus the filesystem driver, e.g. 'gcsfs' for "
                f"gs://); install it or use a local storage_path"
            ) from e
        self._fs = fsspec.filesystem(scheme)
        self._scheme = scheme

    def _strip(self, path: str) -> str:
        return path.split("://", 1)[1] if "://" in path else path

    def write_bytes(self, path: str, data: bytes) -> str:
        with self._fs.open(self._strip(path), "wb") as f:
            f.write(data)
        return path

    def write_chunks(self, path: str, chunks: Iterable[bytes]) -> str:
        p = self._strip(path)
        # Uncommitted until every chunk is in: an object store then shows
        # the whole payload or nothing (the upload is aborted on an error).
        f = self._fs.open(p, "wb", autocommit=False)
        try:
            for chunk in chunks:
                f.write(chunk)
            f.close()
        except BaseException:
            f.discard()
            if getattr(f, "autocommit", True):
                # A filesystem that publishes at open (fsspec's memory://
                # has no deferred commit): take the partial object away.
                self._fs.rm(p)
            raise
        f.commit()
        return path

    def read_bytes(self, path: str) -> Optional[bytes]:
        p = self._strip(path)
        if not self._fs.exists(p):
            return None
        with self._fs.open(p, "rb") as f:
            return f.read()

    def exists(self, path: str) -> bool:
        return self._fs.exists(self._strip(path))

    def listdir(self, path: str) -> List[str]:
        p = self._strip(path)
        if not self._fs.exists(p):
            return []
        return sorted(posixpath.basename(e.rstrip("/"))
                      for e in self._fs.ls(p, detail=False))

    def delete(self, path: str) -> None:
        p = self._strip(path)
        if self._fs.exists(p):
            self._fs.rm(p)


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded-budget exponential backoff for transient storage faults.

    ``attempts`` is the TOTAL number of tries (1 = no retry).  Delay before
    retry k (1-based) is ``base_delay_s * 2**(k-1)`` capped at
    ``max_delay_s``, plus a deterministic jitter in ``[0, jitter * delay]``
    derived from the operation key — reproducible under a seeded chaos
    plan, decorrelated across concurrent writers against real storage.
    """

    attempts: int = 4
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    jitter: float = 0.5
    retry_on: Tuple[type, ...] = field(default=(OSError, TimeoutError))

    def delay_for(self, attempt: int, key: str = "") -> float:
        delay = min(self.base_delay_s * (2 ** attempt), self.max_delay_s)
        if self.jitter > 0:
            h = hashlib.sha256(f"{key}/{attempt}".encode()).digest()
            frac = int.from_bytes(h[:8], "little") / 2**64
            delay += self.jitter * delay * frac
        return delay


DEFAULT_RETRY_POLICY = RetryPolicy()

# Module-level knobs, both consulted by get_storage on every call:
# the fault wrapper is chaos.py's injection point; the retry policy is the
# process-wide default (None disables retries entirely).
_fault_wrapper: Optional[Callable[[StorageBackend], StorageBackend]] = None
_default_retry_policy: Optional[RetryPolicy] = DEFAULT_RETRY_POLICY


def set_fault_wrapper(
    wrapper: Optional[Callable[[StorageBackend], StorageBackend]],
) -> None:
    """Install (or clear, with None) a backend wrapper applied by
    ``get_storage`` INSIDE the retry layer — chaos.py's choke point."""
    global _fault_wrapper
    _fault_wrapper = wrapper


def set_default_retry_policy(policy: Optional[RetryPolicy]) -> None:
    """Process-wide retry policy for all storage access (None disables)."""
    global _default_retry_policy
    _default_retry_policy = policy


def retry_call(fn: Callable, *args, policy: Optional[RetryPolicy] = None,
               key: str = "", log: Optional[Callable[[str], None]] = None,
               **kwargs):
    """Run ``fn(*args, **kwargs)`` under ``policy`` (default: the process
    policy).  Retries only the policy's exception types; the final attempt's
    error propagates unchanged so callers keep their existing error paths."""
    policy = policy if policy is not None else _default_retry_policy
    if policy is None or policy.attempts <= 1:
        return fn(*args, **kwargs)
    last_exc: Optional[BaseException] = None
    for attempt in range(policy.attempts):
        try:
            return fn(*args, **kwargs)
        except policy.retry_on as exc:
            last_exc = exc
            if attempt == policy.attempts - 1:
                raise
            delay = policy.delay_for(attempt, key)
            if log is not None:
                log(
                    f"transient storage fault (attempt "
                    f"{attempt + 1}/{policy.attempts}): {exc!r}; retrying "
                    f"in {delay:.3f}s"
                )
            time.sleep(delay)
    raise last_exc  # pragma: no cover - loop always returns or raises


class RetryingStorage(StorageBackend):
    """Decorator adding the retry policy to every byte operation.

    Wraps any backend (including a chaos ``FaultyStorage``); ``join`` and
    identity-ish helpers delegate straight through.
    """

    def __init__(self, inner: StorageBackend,
                 policy: Optional[RetryPolicy] = None):
        self.inner = inner
        self.policy = policy or DEFAULT_RETRY_POLICY

    def _retry(self, op: str, fn: Callable, path: str, *args):
        return retry_call(fn, path, *args, policy=self.policy,
                          key=f"{op}:{path}")

    def write_bytes(self, path: str, data: bytes) -> str:
        return self._retry("write", self.inner.write_bytes, path, data)

    def write_chunks(self, path: str, chunks: Iterable[bytes]) -> str:
        return self._retry("write", self.inner.write_chunks, path, chunks)

    def read_bytes(self, path: str) -> Optional[bytes]:
        return self._retry("read", self.inner.read_bytes, path)

    def exists(self, path: str) -> bool:
        return self._retry("exists", self.inner.exists, path)

    def listdir(self, path: str) -> List[str]:
        return self._retry("listdir", self.inner.listdir, path)

    def delete(self, path: str) -> None:
        return self._retry("delete", self.inner.delete, path)

    def join(self, *parts: str) -> str:
        return self.inner.join(*parts)


_local = LocalStorage()
_memory = MemoryStorage()
_fsspec_cache: Dict[str, FsspecStorage] = {}


def _raw_storage(path: str) -> Tuple[StorageBackend, str]:
    if "://" not in path:
        return _local, path
    scheme, rest = path.split("://", 1)
    if scheme == "file":
        return _local, rest
    if scheme == "mem":
        return _memory, path  # keep full mem:// key
    backend = _fsspec_cache.get(scheme)
    if backend is None:
        backend = _fsspec_cache[scheme] = FsspecStorage(scheme)
    return backend, path


def get_storage(path: str) -> Tuple[StorageBackend, str]:
    """Backend + normalized path for ``path``, dispatched on its scheme.

    The returned backend is wrapped with the active fault layer (chaos
    injection, when installed) and the process retry policy, in that order
    — retries absorb injected transient faults exactly as real ones.
    """
    backend, p = _raw_storage(path)
    if _fault_wrapper is not None:
        backend = _fault_wrapper(backend)
    if _default_retry_policy is not None:
        backend = RetryingStorage(backend, _default_retry_policy)
    return backend, p

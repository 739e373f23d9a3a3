"""Pytree checkpoint save/restore over pluggable storage — the
compatibility shim over the :mod:`distributed_machine_learning_tpu.ckpt`
subsystem.

Two on-disk formats, one API:

* **legacy msgpack blob** (``ckpt_NNNNNN.msgpack`` + ``.manifest.json``
  sha256 sidecar) — flax msgpack of the whole pytree, written atomically;
  the format every pre-``ckpt/`` experiment on disk already uses.  The
  save is streamed, flax's bytes: headers and each leaf's own memory go to
  storage and into the sha256 in turn, and the payload is never built.
* **sharded generation** (``gen_NNNNNN/`` — per-shard chunk files + JSON
  index + COMMIT marker, ``ckpt/format.py``) — async-friendly and
  topology-portable (restore onto a different mesh/device count).

``save_checkpoint``/``load_checkpoint`` dispatch on the path;
generation-walking logic (``find_latest_checkpoint``,
``newest_valid_checkpoint``, ``load_checkpoint_with_fallback``,
``prune_checkpoints``) delegates to ``ckpt.manager``, which understands
both formats in one directory — so executors, cluster requeue, resume, and
serve export all keep their call sites while gaining sharded checkpoints.
Which format new checkpoints use is the caller's choice via
``checkpoint_path(..., checkpoint_format=...)`` (``tune.run`` exposes it).

No pickle anywhere on this path — both formats stay process- and
framework-portable (enforced by the import-guard test in CI).
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import queue
import re
import threading
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from flax import serialization

from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.ckpt import format as _sharded_fmt
from distributed_machine_learning_tpu.ckpt.format import (  # noqa: F401
    CheckpointCorruptionError,
)
from distributed_machine_learning_tpu.ckpt.metrics import get_metrics
from distributed_machine_learning_tpu.tune.storage import get_storage

_CKPT_RE = re.compile(r"^ckpt_(\d+)\.msgpack$")

MANIFEST_SUFFIX = ".manifest.json"


def manifest_path_for(path: str) -> str:
    return path + MANIFEST_SUFFIX


def _to_host(tree):
    """Device arrays -> numpy so serialization never hangs on device buffers."""
    return jax.tree.map(
        lambda x: np.asarray(x) if hasattr(x, "shape") else x, tree
    )


def _is_sharded(path: str) -> bool:
    """Format dispatch for one path: generation-dir name, else the
    ``.msgpack`` suffix decides cheaply, else probe for an index file."""
    base = os.path.basename(str(path).rstrip("/"))
    if _sharded_fmt.GEN_RE.match(base):
        return True
    if base.endswith(".msgpack") or base.endswith(".ckpt"):
        return False
    return _sharded_fmt.is_sharded_path(path)


# -- the msgpack road, streamed ------------------------------------------------
#
# flax's encoding is small: ``to_state_dict`` turns every container into a
# dict with ``str`` keys, a dict is a msgpack map, an array is ext type 1
# around an inner ``packb((shape, dtype.name, bytes))`` (an array over
# ``MAX_CHUNK_SIZE`` a dictionary of such chunks), and every other leaf a
# few bytes.  Every header follows from a leaf's shape and dtype alone, so
# one walk over the tree hands storage the headers and, between them, each
# leaf's own memory.

# Leaves under this many bytes are copied into the run of headers around
# them: one chunk per bias would be a hash call and a write call each.
_COALESCE_BYTES = 1 << 16

# The leaves msgpack takes as they are under ``strict_types`` (exact types:
# a subclass goes to flax's ``default``), and the two flax wraps itself.
_SCALAR_TYPES = (type(None), bool, int, float, str, complex)


def _ext_header(code: int, n: int) -> bytes:
    """msgpack's header of an ext value of ``n`` payload bytes."""
    if n in (1, 2, 4, 8, 16):
        return bytes((0xD4 + n.bit_length() - 1, code))
    if n <= 0xFF:
        return bytes((0xC7, n, code))
    if n <= 0xFFFF:
        return b"\xc8" + n.to_bytes(2, "big") + bytes((code,))
    return b"\xc9" + n.to_bytes(4, "big") + bytes((code,))


def _bin_header(n: int) -> bytes:
    if n <= 0xFF:
        return bytes((0xC4, n))
    if n <= 0xFFFF:
        return b"\xc5" + n.to_bytes(2, "big")
    return b"\xc6" + n.to_bytes(4, "big")


def _array_header(shape, dtype, nbytes: int) -> bytes:
    """Everything of flax's encoding of an array but its ``nbytes`` of
    row-major data: the ext header, then the inner 3-tuple's head."""
    inner = b"\x93" + msgpack.packb((tuple(shape), dtype.name))[1:]
    inner += _bin_header(nbytes)
    return _ext_header(1, len(inner) + nbytes) + inner


def _kind(x) -> Optional[str]:
    """How the streamer encodes a node of a state dict ("map", "array",
    "scalar"), or None for what it leaves to flax."""
    if type(x) is dict:
        return "map"
    if type(x) is np.ndarray or isinstance(x, (jax.Array, np.generic)):
        dtype = x.dtype
        plain = (isinstance(dtype, np.dtype) and not dtype.hasobject
                 and dtype.fields is None)
        return "array" if plain else None
    return "scalar" if type(x) in _SCALAR_TYPES else None


def _streamable(state) -> bool:
    """Whether every node of ``state`` is one the streamer encodes."""
    kind = _kind(state)
    if kind != "map":
        return kind is not None
    return all(
        type(key) is str and _streamable(value)
        for key, value in state.items()
    )


class _Slice(NamedTuple):
    """Elements ``start:stop`` of a leaf, flattened row-major (the whole
    leaf when ``stop`` is None), whose bytes the stream reads in turn."""

    leaf: Any
    start: int
    stop: Optional[int]
    nbytes: int

    def read(self) -> memoryview:
        """The slice's bytes, a view of the host array wherever it is
        contiguous (a device leaf is read here, whole).  bfloat16 and its
        kin export no buffer, so the view is taken as ``uint8``."""
        arr = np.ascontiguousarray(np.asarray(self.leaf)).reshape(-1)
        if self.stop is not None:
            arr = arr[self.start:self.stop]
        return memoryview(arr.view(np.uint8))


def _pieces(x, packer: msgpack.Packer):
    """flax's msgpack payload of a streamable state dict, in order and as
    it is asked for: ``bytes`` (headers, keys, scalar leaves) and a
    :class:`_Slice` wherever an array's data goes; nothing is read from a
    device.  Lazy, so that the walk over a large tree is spread through the
    write and never holds the interpreter lock for long."""
    kind = _kind(x)
    if kind == "map":
        yield packer.pack_map_header(len(x))
        for key, value in x.items():
            yield packer.pack(key)
            yield from _pieces(value, packer)
    elif kind == "scalar":
        yield serialization.msgpack_serialize(x)
    elif x.size * x.dtype.itemsize <= serialization.MAX_CHUNK_SIZE:
        nbytes = x.size * x.dtype.itemsize
        yield _array_header(x.shape, x.dtype, nbytes)
        if nbytes:
            yield _Slice(x, 0, None, nbytes)
    else:  # flax's ``_chunk``: flat slices under the msgpack limit
        itemsize = x.dtype.itemsize
        if type(x) is np.ndarray:
            x = np.ascontiguousarray(x)  # once, not once a slice
        step = max(1, int(serialization.MAX_CHUNK_SIZE / itemsize))
        starts = range(0, x.size, step)
        yield (
            packer.pack_map_header(3)
            + packer.pack("__msgpack_chunked_array__") + packer.pack(True)
            + packer.pack("shape")
            + packer.pack({str(i): int(n) for i, n in enumerate(x.shape)})
            + packer.pack("chunks") + packer.pack_map_header(len(starts))
        )
        for i, start in enumerate(starts):
            stop = min(start + step, x.size)
            nbytes = (stop - start) * itemsize
            yield packer.pack(str(i)) + _array_header(
                (stop - start,), x.dtype, nbytes
            )
            yield _Slice(x, start, stop, nbytes)


class _PayloadStream:
    """A streamable state dict's payload as the chunks a storage backend
    writes, hashed as they pass: ``sha256``, ``nbytes``, ``chunks`` and
    ``serialize_s`` are those of the newest pass (a backend that retries
    iterates again, from the start).

    A chunk is valid until the next is asked for.  Beyond the tree itself
    one leaf is in host memory at a time (jax keeps a device leaf's host
    copy with the array, until the caller drops the tree); the next
    device leaf's copy is started while this one is hashed and written."""

    def __init__(self, state):
        self._state = state
        self._start_pass()

    def _start_pass(self):
        self.sha256 = hashlib.sha256()
        self.nbytes = self.chunks = 0
        self.serialize_s = 0.0

    def _chunk(self, data):
        t0 = time.perf_counter()
        self.sha256.update(data)  # over 2 KB: without the interpreter lock
        self.serialize_s += time.perf_counter() - t0
        self.nbytes += len(data)
        self.chunks += 1
        return data

    def __iter__(self):
        from distributed_machine_learning_tpu import obs

        self._start_pass()
        pieces = _pieces(self._state, msgpack.Packer())
        coming: Deque = collections.deque()

        def pull_through_next_slice():
            for piece in pieces:
                coming.append(piece)
                if isinstance(piece, _Slice):
                    if isinstance(piece.leaf, jax.Array):
                        piece.leaf.copy_to_host_async()
                    return

        pull_through_next_slice()
        run = bytearray()
        while coming:
            piece = coming.popleft()
            if not isinstance(piece, _Slice):
                run += piece
                continue
            pull_through_next_slice()  # its read-back runs beside this one
            if isinstance(piece.leaf, jax.Array):
                with obs.span("ckpt.device_get"):
                    data = piece.read()
            else:
                t0 = time.perf_counter()
                data = piece.read()  # a copy where the leaf is a strided view
                self.serialize_s += time.perf_counter() - t0
            if piece.nbytes < _COALESCE_BYTES:
                run += data
                data = b""
                if len(run) < _COALESCE_BYTES:
                    continue
            yield self._chunk(bytes(run))
            run = bytearray()
            if data:
                yield self._chunk(data)
        if run:
            yield self._chunk(bytes(run))


def save_checkpoint(path: str, tree: Dict[str, Any]) -> str:
    """Serialize a pytree dict to ``path`` (any storage scheme). Returns path.

    A ``gen_NNNNNN`` path writes the sharded chunked format (atomic COMMIT
    protocol); anything else writes the legacy msgpack blob whose
    ``<path>.manifest.json`` sidecar (sha256 + byte count) lands AFTER the
    payload — a crash between the two leaves a checkpoint that is merely
    unverifiable, never a manifest pointing at absent data.

    The blob is flax's bytes (``serialization.to_bytes`` of the tree read
    to the host), streamed: one walk over the tree hands storage the
    headers and each leaf's own memory in turn and hashes them as they
    pass, so no copy of the state is built.  A tree with a leaf the
    streamer does not cover is packed by flax itself, whole, as before.
    """
    from distributed_machine_learning_tpu import obs

    if _is_sharded(path):
        with obs.span("ckpt.save", {"format": "sharded"}):
            return _sharded_fmt.save_sharded(path, tree)
    with obs.span("ckpt.save", {"format": "msgpack"}) as save_span:
        t0 = time.time()
        backend, p = get_storage(path)
        # jax's map sorts every dict's keys, as the read to the host did.
        state = serialization.to_state_dict(jax.tree.map(lambda x: x, tree))
        streamed = _streamable(state)
        save_span.set("streamed", streamed)
        with obs.span("ckpt.write") as write_span:
            if streamed:
                stream = _PayloadStream(state)
                backend.write_chunks(p, stream)
                nbytes, digest = stream.nbytes, stream.sha256.hexdigest()
                chunks, serialize_s = stream.chunks, stream.serialize_s
            else:
                with obs.span("ckpt.device_get"):
                    host_tree = _to_host(tree)
                t1 = time.perf_counter()
                payload = serialization.to_bytes(host_tree)
                del host_tree  # not held through the write
                nbytes = len(payload)
                digest = hashlib.sha256(payload).hexdigest()
                chunks, serialize_s = 1, time.perf_counter() - t1
                backend.write_bytes(p, payload)
            manifest = {"sha256": digest, "bytes": nbytes,
                        "format": "flax-msgpack"}
            backend.write_bytes(
                manifest_path_for(p), json.dumps(manifest).encode()
            )
            write_span.set("bytes", nbytes).set("chunks", chunks).set(
                "serialize_s", round(serialize_s, 6)
            )
        save_span.set("bytes", nbytes)
        get_metrics().record_save(
            time.time() - t0, nbytes, 1, streamed=streamed
        )
    return path


def load_checkpoint(
    path: str, verify: bool = True, shardings=None,
) -> Optional[Dict[str, Any]]:
    """Decode a checkpoint without needing a target template.

    Sharded generations restore through ``ckpt.format.load_sharded`` —
    pass ``shardings`` to reshard array leaves onto a target mesh; without
    it arrays gather to full numpy (bit-identical to what was saved,
    whatever topology saved it).  Legacy blobs ignore ``shardings`` (they
    are host-gathered by construction).

    With ``verify`` (default) integrity is checked before decoding —
    manifest sha256 for msgpack (a missing manifest demotes to
    decode-checking), COMMIT + per-chunk sha256 for sharded — and damage
    raises :class:`CheckpointCorruptionError`.
    """
    if not path:
        return None
    from distributed_machine_learning_tpu import obs

    if _is_sharded(path):
        with obs.span("ckpt.restore", {"format": "sharded"}):
            return _sharded_fmt.load_sharded(
                path, verify=verify, shardings=shardings
            )
    with obs.span("ckpt.restore", {"format": "msgpack"}):
        return _load_msgpack(path, verify)


def _load_msgpack(path: str, verify: bool) -> Optional[Dict[str, Any]]:
    t0 = time.time()
    backend, p = get_storage(path)
    data = backend.read_bytes(p)
    if data is None:
        return None
    if verify:
        raw = backend.read_bytes(manifest_path_for(p))
        if raw is not None:
            try:
                expected = json.loads(raw).get("sha256")
            except ValueError:
                expected = None
            if expected is not None and (
                hashlib.sha256(data).hexdigest() != expected
            ):
                raise CheckpointCorruptionError(
                    f"checksum mismatch for {path} "
                    f"({len(data)} bytes on storage)"
                )
        try:
            tree = serialization.msgpack_restore(data)
        except Exception as exc:  # noqa: BLE001 - damaged bytes, any decoder error
            raise CheckpointCorruptionError(
                f"undecodable checkpoint at {path}: {exc!r}"
            ) from exc
        get_metrics().record_restore(time.time() - t0, len(data))
        return tree
    tree = serialization.msgpack_restore(data)
    get_metrics().record_restore(time.time() - t0, len(data))
    return tree


def verify_checkpoint(path: str) -> bool:
    """True if ``path`` exists and passes its integrity checks."""
    try:
        return load_checkpoint(path) is not None
    except CheckpointCorruptionError:
        return False


def _iteration_of(path: str) -> int:
    from distributed_machine_learning_tpu.ckpt.manager import step_of_path

    return step_of_path(path)


def load_checkpoint_with_fallback(
    path: Optional[str], directory: Optional[str] = None, log=None,
    shardings=None,
) -> Tuple[Optional[Dict[str, Any]], Optional[str], int]:
    """Restore ``path``; on corruption fall back to the newest
    valid generation (either format) under ``directory``.

    Returns ``(tree, used_path, used_iteration)`` — ``(None, None, 0)``
    when nothing restorable survives (the caller restarts from scratch).
    The corrupt file is left in place (forensics; retention prunes it like
    any old generation) — callers must rewind their iteration bookkeeping
    to ``used_iteration``.
    """
    from distributed_machine_learning_tpu.ckpt.manager import (
        restore_with_fallback,
    )

    emit = log or (lambda msg: print(f"[checkpoint] {msg}", flush=True))
    return restore_with_fallback(path, directory, log=emit,
                                 shardings=shardings)


def restore_into(template, tree: Dict[str, Any]):
    """Restore a raw decoded dict into ``template``'s pytree structure/dtypes."""
    return serialization.from_state_dict(template, tree)


def checkpoint_path(directory: str, iteration: int,
                    checkpoint_format: str = "msgpack") -> str:
    from distributed_machine_learning_tpu.ckpt.manager import step_path

    return step_path(directory, iteration, checkpoint_format)


def find_latest_checkpoint(directory: str):
    """(path, iteration) of the newest generation (either format) under
    ``directory``, or (None, 0) when there is none — how a resumed
    experiment rediscovers each trial's restore point."""
    from distributed_machine_learning_tpu.ckpt.manager import (
        latest_generation,
    )

    return latest_generation(directory)


def newest_valid_checkpoint(directory: str, max_iteration=None):
    """(path, iteration) of the newest generation that PASSES its
    integrity check, or (None, 0).  The restore target for trials requeued
    off a silent worker (cluster lease expiry / stall fencing): the lost
    incarnation may have died mid-write, so the newest entry on disk is
    not necessarily a loadable one — sharded generations must be COMMITTED
    and checksum-clean, msgpack blobs must match their manifest.
    ``max_iteration`` skips generations above it (the at-least-once
    fencing guard — see ``quarantine_unreported``)."""
    from distributed_machine_learning_tpu.ckpt.manager import (
        newest_valid_generation,
    )

    return newest_valid_generation(directory, max_step=max_iteration)


def quarantine_unreported(directory: str, last_reported_iteration: int,
                          tag: str = "", log=None) -> int:
    """Rename every generation newer than ``last_reported_iteration`` out
    of the generation namespace (prefix ``fenced[.tag].``) — they were
    written by a fenced/expired incarnation for epochs whose reports never
    reached the driver, and restoring one would skip those reports forever
    (the at-least-once fencing race, docs/operations.md).  Returns the
    count quarantined; bytes stay on storage for forensics."""
    from distributed_machine_learning_tpu.ckpt.manager import (
        quarantine_generations_above,
    )

    return quarantine_generations_above(
        directory, last_reported_iteration, tag=tag, log=log
    )


def cleanup_uncommitted(directory: str, log=None) -> int:
    """Remove torn sharded generations (no COMMIT) — safe only at start,
    before any writer is live.  See ``ckpt.manager.cleanup_uncommitted``."""
    from distributed_machine_learning_tpu.ckpt.manager import (
        cleanup_uncommitted as _cleanup,
    )

    return _cleanup(directory, log=log)


def _abspath_unless_remote(path: str) -> str:
    """abspath local paths only — os.path.abspath would mangle gs://-style
    URLs into '<cwd>/gs:/...' (orbax handles remote schemes itself)."""
    if re.match(r"^[a-z0-9]+://", path):
        return path
    return os.path.abspath(path)


def export_orbax(checkpoint_path: str, out_dir: str) -> str:
    """Convert a framework checkpoint to an orbax StandardCheckpoint.

    Interop bridge OUT of the framework: the pytree (params / opt_state /
    batch_stats / scalars) becomes a directory any orbax-consuming JAX
    stack restores directly — handing a tuned model to a separate
    serving/fine-tuning codebase without importing this package.  Works
    from either format (a sharded generation gathers first).  Returns
    ``out_dir``. Raises ImportError if orbax is absent (it is an optional
    dependency).
    """
    import orbax.checkpoint as ocp

    tree = load_checkpoint(checkpoint_path)
    if tree is None:
        raise FileNotFoundError(f"no checkpoint at {checkpoint_path!r}")
    out_dir = _abspath_unless_remote(out_dir)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(out_dir, tree)
    return out_dir


def import_orbax(src_dir: str) -> Dict[str, Any]:
    """Restore an orbax StandardCheckpoint into a raw pytree dict —
    the inverse bridge (``restore_into`` then shapes it to a template)."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(_abspath_unless_remote(src_dir))


@jax.jit
def _copy_on_device(leaves):
    """A fresh buffer of every array of the list, on the devices and in
    the layout of its original, in one program (the list shares a set of
    devices).  No argument is donated, so no result may alias one.  At
    module level, so that jax's own cache (shapes, dtypes, shardings)
    serves every writer of the process; it holds no array."""
    return [jnp.copy(x) for x in leaves]


class AsyncCheckpointWriter:
    """Overlap checkpoint writes with training (orbax-style async save).

    ``submit(path, tree)`` returns immediately; the device->host transfer,
    serialization, and storage write run on ONE background thread, in
    submission order (both formats — a ``gen_NNNNNN`` path writes the
    sharded chunked format).  The trial thread goes straight back to
    training — at real checkpoint sizes the epoch that used to stall
    behind the write now runs concurrently with it, which the
    ``ckpt.metrics`` overlap counters make observable.

    Correctness contract (why this is safe in-process):
    * ``submit`` snapshots EVERY array leaf: jax arrays get a device-side
      copy (cheap — HBM bandwidth; the D2H transfer stays on the writer
      thread), because the caller's train step donates its buffers
      (``donate_argnums``) and the next step would delete the submitted
      arrays out from under the serializer ("Array has been deleted" —
      donation is a no-op on CPU, so only real TPU runs hit it). Mutable
      numpy leaves are host-copied for the same reason.
    * The device-side copy is ONE dispatch a device set, not one a leaf:
      the jax leaves are grouped by the devices that hold them
      (``x.sharding.device_set``) and each group goes through one compiled
      program that returns a fresh buffer of every leaf
      (:func:`_copy_on_device`: a single-chip trial is one group, a trial
      sharded over a mesh one group over the mesh, a tree that spans
      unrelated devices one dispatch each).  Nothing is donated to that
      program, so no result can alias an argument — a copy that shared
      its original's buffer would be deleted with it — and every copy
      keeps its original's sharding, so the sharded format writes the
      chunks it always did.  The copy is enqueued before ``submit``
      returns, that is before the caller can dispatch the step that
      donates the originals.  jax keys the program on the leaves' shapes,
      dtypes and shardings: a sweep compiles it once a shape class, and a
      later writer in the process finds it.
    * A state that leaves its device no room for a second copy is read to
      the host inside ``submit`` instead, leaf by leaf (``to_host`` on the
      ``report.ckpt_snapshot`` span; no dispatch).
    * A reader who might race a pending write (retry restore, PBT exploit
      of a peer's checkpoint) calls ``wait(path)`` first; the threaded
      executor routes every restore through it. Cross-process restores
      (cluster workers) keep synchronous saves instead — a remote reader
      cannot wait on this process's queue.
    * Write errors re-raise on ``wait``; ``close`` logs any unclaimed
      errors through ``log`` (or re-raises with ``raise_errors=True``) —
      never a silent drop.
    """

    def __init__(self, log=None):
        self._q: "queue.Queue" = queue.Queue()
        self._lock = named_lock("tune.checkpoint.writer")
        self._pending: Dict[str, threading.Event] = {}
        self._errors: Dict[str, BaseException] = {}
        self._log = log or (lambda msg: print(
            f"[checkpoint] {msg}", flush=True
        ))
        self._thread = threading.Thread(
            target=self._worker, name="ckpt-writer", daemon=True
        )
        self._thread.start()

    def _worker(self):
        metrics = get_metrics()
        while True:
            item = self._q.get()
            if item is None:
                return
            path, tree, done, steps_at_submit = item
            try:
                save_checkpoint(path, tree)
                metrics.record_async_completion(steps_at_submit)
            except BaseException as exc:  # noqa: BLE001 - surfaced on wait
                metrics.add("save_errors")
                with self._lock:
                    self._errors[path] = exc
            finally:
                with self._lock:
                    self._pending.pop(path, None)
                done.set()
            # Not held while the queue is empty: the snapshot is a second
            # copy of the state on the device (and of what was read of it
            # on the host).
            item = tree = None

    @staticmethod
    def _snapshot_leaf(x):
        """A host leaf's own copy (the caller may write into its numpy
        buffers); jax leaves are copied together, Python leaves pass."""
        return x.copy() if isinstance(x, np.ndarray) else x

    def _snapshot_on_device(self, leaves: List[Any]) -> Tuple[List[Any], int]:
        """``leaves`` with every jax leaf replaced by a device-side copy
        and every numpy leaf by a host copy, and the number of dispatches
        that took: one a set of devices.  The D2H read of the copies stays
        on the writer thread, and donation of the originals cannot delete
        them."""
        groups: Dict[Any, List[int]] = {}
        for i, x in enumerate(leaves):
            if isinstance(x, jax.Array):
                groups.setdefault(
                    frozenset(x.sharding.device_set), []
                ).append(i)
        snapshot = [self._snapshot_leaf(x) for x in leaves]
        for devices, members in groups.items():
            copies = _copy_on_device([leaves[i] for i in members])
            for i, copy in zip(members, copies):
                # Over a mesh jit names the layout it kept in its own
                # words (a spec without its trailing None): the snapshot
                # carries the original's, which re-wraps the same buffers.
                if len(devices) > 1 and copy.sharding != leaves[i].sharding:
                    copy = jax.device_put(copy, leaves[i].sharding)
                snapshot[i] = copy
        return snapshot, len(groups)

    @staticmethod
    def _device_has_room_for(leaves) -> bool:
        """Whether every device that holds leaves of the tree can hold a
        second copy of its share beside what it holds already (the
        allocator's ``bytes_limit - bytes_in_use``).  A device that reports
        no statistics (the CPU) is taken to have room."""
        need: Dict[Any, int] = {}
        for x in leaves:
            if not isinstance(x, jax.Array):
                continue
            devices = x.sharding.device_set
            if len(devices) == 1:  # the common case, and no shard objects
                (device,) = devices
                need[device] = need.get(device, 0) + x.nbytes
                continue
            for shard in x.addressable_shards:
                need[shard.device] = (
                    need.get(shard.device, 0) + shard.data.nbytes
                )
        for device, nbytes in need.items():
            stats = device.memory_stats() or {}
            if "bytes_limit" in stats and nbytes > (
                stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            ):
                return False
        return True

    def submit(self, path: str, tree: Dict[str, Any]) -> str:
        """Enqueue a write; returns ``path`` immediately."""
        from distributed_machine_learning_tpu import obs

        metrics = get_metrics()
        t0 = time.time()
        leaves, treedef = jax.tree.flatten(tree)
        with obs.span("report.ckpt_snapshot", {"leaves": len(leaves)}) as sp:
            if self._device_has_room_for(leaves):
                snapshot, dispatches = self._snapshot_on_device(leaves)
            else:
                # A state that fills the chip (parameters and optimizer
                # state of a model sized to it) has no room for its copy:
                # read it to the host here, leaf by leaf, before the
                # caller's next step donates the buffers.
                sp.set("to_host", True)
                dispatches = 0
                snapshot = [
                    np.asarray(x) if isinstance(x, jax.Array)
                    else self._snapshot_leaf(x)
                    for x in leaves
                ]
            sp.set("dispatches", dispatches)
            snapshot = treedef.unflatten(snapshot)
        metrics.add("save_block_s", time.time() - t0)
        metrics.add("snapshot_dispatches", dispatches)
        metrics.add("snapshot_leaves", len(leaves))
        done = threading.Event()
        with self._lock:
            self._pending[path] = done
        self._q.put((path, snapshot, done, metrics.step_count()))
        return path

    def wait(self, path: Optional[str] = None,
             timeout: Optional[float] = None) -> bool:
        """Block until ``path`` (or every pending write) is durable; re-raise
        its write error if one occurred. Returns False if ``timeout``
        expired with writes still pending."""
        deadline = None if timeout is None else time.monotonic() + timeout
        if path is None:
            with self._lock:
                events = list(self._pending.values())
            for ev in events:
                left = None if deadline is None else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                if not ev.wait(left):
                    return False
            with self._lock:
                # Pop only what we surface: the raised error is claimed —
                # re-raising it on every later wait() (and re-logging at
                # close()) turns one bad write into a permanent poison
                # (advisor r3). Other paths' errors stay claimable so they
                # are never silently dropped.
                first = next(iter(self._errors), None)
                err = self._errors.pop(first, None) if first else None
            if err is not None:
                raise err
            return True
        with self._lock:
            ev = self._pending.get(path)
        if ev is not None and not ev.wait(
            None if deadline is None else max(deadline - time.monotonic(), 0.0)
        ):
            return False
        with self._lock:
            err = self._errors.pop(path, None)
        if err is not None:
            raise err
        return True

    def close(self, raise_errors: bool = False,
              timeout: Optional[float] = 30.0) -> None:
        """Flush pending writes (bounded by ``timeout``) and stop the worker.

        Unclaimed write errors are logged (or re-raised when
        ``raise_errors``); a write still hung at the deadline is abandoned
        with a log line rather than blocking teardown forever.
        """
        if not self._thread.is_alive():
            return
        flushed = True
        try:
            flushed = self.wait(timeout=timeout)
        except BaseException as exc:
            if raise_errors:
                self._q.put(None)
                self._thread.join(timeout=10)
                raise
            # wait() popped (claimed) the error it raised; surface it here
            # so an unclaimed failure is never silently dropped.
            self._log(
                "WARNING: checkpoint write(s) failed and were never "
                f"waited on; first: {exc!r}"
            )
        if not flushed:
            with self._lock:
                stuck = list(self._pending)
            self._log(
                f"WARNING: abandoning {len(stuck)} hung checkpoint "
                f"write(s) at teardown: {stuck[:3]}"
            )
        # Errors for writes that completed while wait() was timing out on a
        # different pending path can still be unclaimed — log those too.
        with self._lock:
            errors = dict(self._errors)
            self._errors.clear()
        if errors and not raise_errors:
            first_path, first_err = next(iter(errors.items()))
            self._log(
                f"WARNING: {len(errors)} checkpoint write(s) failed and "
                f"were never waited on; first: {first_path}: {first_err!r}"
            )
        self._q.put(None)
        # Only wait for the worker when the queue actually drained — a hung
        # write would pin this join for its full timeout, and the thread is
        # a daemon, so abandoning it is safe.
        if flushed:
            self._thread.join(timeout=10)


def prune_checkpoints(directory: str, keep: int, protect=None,
                      pending_latest: Optional[str] = None) -> int:
    """Keep only the ``keep`` newest generations (either format) in
    ``directory``.

    ``protect`` (a full path, or an iterable of them) is never deleted even if
    old — e.g. a checkpoint another trial's PBT exploit is about to restore.
    ``pending_latest``: a checkpoint path submitted to the async writer but
    possibly not on disk yet — behaviorally an alias for a ``protect`` entry,
    kept as the call-site's declaration of an in-flight write.  While it is
    in flight the newest ``keep`` DURABLE generations are all retained —
    deleting them against a write that may still fail (crash, preemption,
    storage error) could leave the trial with zero restorable checkpoints,
    exactly the scenario checkpointing covers.  The on-disk set transiently
    overshoots by up to the executor's write-pipeline depth (``keep``+2
    with the depth-2 pipeline) while writes land; later prunes — and the
    runner's final retention pass after the writer drains — converge it
    back to exactly ``keep``.
    Returns the number of generations deleted.
    """
    from distributed_machine_learning_tpu.ckpt.manager import (
        prune_generations,
    )

    return prune_generations(directory, keep, protect=protect,
                             pending_latest=pending_latest)

"""Sharded, topology-portable checkpoint format.

A checkpoint *generation* is a directory (any ``tune.storage`` scheme)::

    gen_000007/
        L3.0-0.chunk      raw little-endian bytes of one shard of leaf 3
        L3.4-0.chunk      (name = leaf index + the chunk's global start
        ...                offsets, so names are deterministic across hosts
        index.json         and re-saves)
        COMMIT

``index.json`` maps the pytree back together: a JSON skeleton of the tree
(dicts/lists with ``{"__leaf__": n}`` markers), and per leaf its global
shape, dtype, and the chunk table — each chunk's file name, global
``start``/``stop`` offsets, byte count, and sha256.  Non-array leaves
(ints, strings, lists of strings, ...) are stored literally in the index.

Why per-shard chunks instead of one msgpack blob (``tune/checkpoint.py``'s
legacy format): each host serializes only the shards it actually holds
(no all-gather through one host), and a restore reads only the chunks the
*target* sharding needs — which is what makes a checkpoint saved on one
mesh restorable on a different mesh, a different device count, or a single
host (the Orbax design, PAPERS.md).

Commit protocol (atomicity across many files; single-file writes are
already atomic in ``tune.storage``): chunks first, then ``index.json``,
then a ``COMMIT`` marker carrying the index's sha256 — written LAST.  A
save preempted anywhere leaves a generation without a valid ``COMMIT``,
which every reader treats as nonexistent and the
:class:`~distributed_machine_learning_tpu.ckpt.manager.CheckpointManager`
deletes on start.  No pickle anywhere: raw array bytes + JSON keep the
format process- and framework-portable.

Multi-host note: chunk names derive from global offsets and the index's
chunk table is computed from the sharding's ``devices_indices_map`` (which
every process can evaluate), so hosts write disjoint chunk files into the
same directory and process 0 writes the index/COMMIT.  Chunks written by
other hosts carry ``"sha256": null`` in process 0's index (their bytes
never crossed hosts); they are decode-checked on read instead.

Content-addressed mode (ISSUE 20; single-process saves, default on, see
``store.store_enabled``): chunk PAYLOADS land in the sibling content
store instead of per-generation ``*.chunk`` files.  Each chunk record
additionally carries ``"blobs": [{"h": <sha256>, "nbytes": n}, ...]`` —
row-aligned pieces published via ``ContentStore.put_blob``, so a piece
unchanged between generation N and N+1 (or a PBT donor row shared across
population members) is a dedup hit, not a write.  The index records the
store root under ``"store"`` and a ``ckpt-<hash(path)>`` ref points GC at
the generation's manifest; the commit protocol is unchanged (blobs ->
manifest -> ref -> index.json -> COMMIT), restores stay bit-identical,
and multi-process saves keep the legacy chunk-file layout (other hosts'
chunk hashes never cross hosts, so one process cannot name their blobs).
"""

from __future__ import annotations

import hashlib
import json
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distributed_machine_learning_tpu import store as store_lib
from distributed_machine_learning_tpu.ckpt.metrics import get_metrics
from distributed_machine_learning_tpu.tune.storage import get_storage

FORMAT_VERSION = 1
INDEX_NAME = "index.json"
COMMIT_NAME = "COMMIT"
CHUNK_SUFFIX = ".chunk"

GEN_RE = re.compile(r"^gen_(\d+)$")

_LEAF_KEY = "__leaf__"


class CheckpointCorruptionError(Exception):
    """Stored checkpoint bytes fail their checksum or do not decode.

    Canonical definition (``tune.checkpoint`` re-exports it): both formats
    raise the same class so every fallback path catches one thing.
    """


def generation_name(step: int) -> str:
    return f"gen_{int(step):06d}"


def step_of_generation(path: str) -> Optional[int]:
    import posixpath

    m = GEN_RE.match(posixpath.basename(path.rstrip("/")))
    return int(m.group(1)) if m else None


def is_sharded_path(path: str) -> bool:
    """True when ``path`` names a sharded generation directory — by name
    (``gen_NNNNNN``) or by containing an ``index.json``."""
    import posixpath

    base = posixpath.basename(path.rstrip("/"))
    if GEN_RE.match(base):
        return True
    backend, p = get_storage(path)
    return backend.exists(backend.join(p, INDEX_NAME))


# -- dtype portability ---------------------------------------------------------


def _dtype_str(dt) -> str:
    return np.dtype(dt).name


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 & friends live in ml_dtypes (a jax dependency) and may
        # not be registered with bare numpy on every version.
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


# -- host snapshot -------------------------------------------------------------


class HostLeaf:
    """Host-side snapshot of one array leaf: global shape/dtype plus the
    chunks THIS process holds, each a ``(start, stop, ndarray)`` triple in
    global coordinates.  ``remote_chunks`` lists (start, stop) of shards
    owned by other hosts (chunk table entries without local bytes).
    ``partition`` records the leaf's PartitionSpec (JSON-rendered, with
    the mesh axis sizes) when the source array carried a NamedSharding —
    the rule-derived layout rides in the index so a restore can rebuild
    it without re-resolving the rule table."""

    __slots__ = ("shape", "dtype", "chunks", "remote_chunks", "partition")

    def __init__(self, shape, dtype, chunks, remote_chunks=(),
                 partition=None):
        self.shape = tuple(int(d) for d in shape)
        self.dtype = _dtype_str(dtype)
        self.chunks: List[Tuple[Tuple[int, ...], Tuple[int, ...], np.ndarray]] = chunks
        self.remote_chunks: List[Tuple[Tuple[int, ...], Tuple[int, ...]]] = list(
            remote_chunks
        )
        self.partition = partition


def _partition_of(x) -> Optional[Dict[str, Any]]:
    """``{"spec": [...], "mesh": {axis: size}}`` for a NamedSharding-backed
    jax.Array; None otherwise (host arrays, single-device placements)."""
    sharding = getattr(x, "sharding", None)
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return None
    from distributed_machine_learning_tpu.parallel.partition import (
        mesh_axis_sizes,
        spec_to_jsonable,
    )

    try:
        return {
            "spec": spec_to_jsonable(spec),
            "mesh": mesh_axis_sizes(mesh),
        }
    except Exception:  # noqa: BLE001 - layout metadata is best-effort
        return None


def _norm_index(index, shape) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """A jax shard index (tuple of slices) -> concrete (start, stop)."""
    start, stop = [], []
    for sl, dim in zip(index, shape):
        start.append(int(sl.start) if sl.start is not None else 0)
        stop.append(int(sl.stop) if sl.stop is not None else int(dim))
    return tuple(start), tuple(stop)


def _is_jax_array(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.Array)
    except Exception:  # pragma: no cover - jax always present here
        return False


def snapshot_leaf(x):
    """Array-like -> :class:`HostLeaf` (device->host COPY happens HERE, so
    an async writer that snapshots at submit time is donation-safe);
    anything else is returned as a literal.

    The copies below must be real copies, never views: ``np.asarray`` on a
    CPU-backed ``jax.Array`` aliases the device buffer zero-copy, and a
    donated buffer (``donate_argnums``) is reused in place by later train
    steps — an aliasing snapshot would serialize FUTURE state under a past
    generation's name (observed: an epoch-6 population checkpoint carrying
    epoch-8 optimizer counts)."""
    if _is_jax_array(x):
        shape = tuple(x.shape)
        shards = getattr(x, "addressable_shards", None)
        if shards:
            chunks: Dict[Tuple, Tuple] = {}
            for s in shards:
                start, stop = _norm_index(s.index, shape)
                key = (start, stop)
                # One writer per distinct global slice: replicas beyond
                # replica 0 hold identical bytes.
                if s.replica_id != 0 or key in chunks:
                    continue
                chunks[key] = (start, stop, np.array(s.data, copy=True))
            remote = []
            try:
                import jax

                if jax.process_count() > 1:  # pragma: no cover - multihost
                    seen = set(chunks)
                    for idx in x.sharding.devices_indices_map(shape).values():
                        start, stop = _norm_index(idx, shape)
                        if (start, stop) not in seen:
                            seen.add((start, stop))
                            remote.append((start, stop))
            except Exception:
                remote = []
            return HostLeaf(shape, x.dtype, list(chunks.values()), remote,
                            partition=_partition_of(x))
        arr = np.array(x, copy=True)
        return HostLeaf(
            arr.shape, arr.dtype,
            [(tuple(0 for _ in arr.shape), tuple(arr.shape), arr)],
            partition=_partition_of(x),
        )
    if isinstance(x, (np.ndarray, np.generic)):
        arr = np.asarray(x)
        return HostLeaf(
            arr.shape, arr.dtype,
            [(tuple(0 for _ in arr.shape), tuple(arr.shape), arr.copy())],
        )
    return x


def snapshot_tree(tree) -> Tuple[Any, List[Any]]:
    """Walk ``tree`` into a JSON skeleton plus a leaf list of
    :class:`HostLeaf` / literal values.

    The tree is normalized through flax's ``to_state_dict`` first (tuples
    and lists become index-keyed dicts, custom nodes their state dicts) so
    a sharded restore returns EXACTLY the same container shapes as the
    legacy msgpack restore — every ``restore_into(template, tree)`` call
    site works unchanged whichever format wrote the checkpoint."""
    from flax import serialization

    tree = serialization.to_state_dict(tree)
    leaves: List[Any] = []

    def walk(node):
        if isinstance(node, dict):
            return {str(k): walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)) and not _leaf_like(node):
            return [walk(v) for v in node]
        leaves.append(snapshot_leaf(node))
        return {_LEAF_KEY: len(leaves) - 1}

    def _leaf_like(node) -> bool:
        # Flat lists of scalars/strings (e.g. trial_ids) stay literal
        # leaves; lists containing containers or arrays are structure.
        return all(
            isinstance(v, (str, int, float, bool)) or v is None for v in node
        )

    return walk(tree), leaves


# -- save ----------------------------------------------------------------------


def _chunk_file_name(leaf_idx: int, start: Sequence[int]) -> str:
    offs = "-".join(str(int(s)) for s in start) or "0"
    return f"L{leaf_idx}.{offs}{CHUNK_SUFFIX}"


def _mh_barrier(name: str) -> None:
    """Order a multi-process save's phases (no-op single-process).

    The commit protocol over many WRITERS needs two fences the
    single-process path gets for free from program order: every process's
    stale-COMMIT delete must land before ANY chunk is written (a late
    starter's delete must never remove the marker process 0 just wrote —
    observed in the 2-process probe), and every process's chunks must land
    before process 0 writes the index/COMMIT that names them."""
    import jax

    try:
        nproc = jax.process_count()
    except Exception:  # pragma: no cover - pre-init
        return
    if nproc <= 1:
        return
    from distributed_machine_learning_tpu.multihost.runtime import barrier

    barrier(name)


def _cas_for(path: str) -> Optional["store_lib.ContentStore"]:
    """The content store serving ``path``'s CAS write path — None when
    the store is disabled (``DML_STORE_CKPT=0``) or the save spans
    processes (other hosts' chunk hashes never cross hosts, so one
    process cannot publish a shared blob namespace)."""
    if not store_lib.store_enabled():
        return None
    try:
        import jax

        if jax.process_count() > 1:  # pragma: no cover - multihost
            return None
    except Exception:  # pragma: no cover - pre-init
        pass
    return store_lib.get_store(store_lib.store_root_for(path))


def _row_stride(arr: np.ndarray) -> int:
    """Byte width of one leading-axis row (0 for scalars) — the piece
    boundary that keeps PBT donor rows and unchanged row ranges hashing
    to the same blobs across writers."""
    if arr.ndim < 1:
        return 0
    return int(np.prod(arr.shape[1:], dtype=np.int64)) * arr.dtype.itemsize


def write_snapshot(path: str, skeleton, leaves: List[Any]) -> Tuple[int, int]:
    """Write a snapshotted tree as one generation under ``path``; returns
    ``(bytes_written, chunks_written)``.  Order is the commit protocol:
    chunk payloads -> (CAS mode: manifest -> ref) -> index.json -> COMMIT
    (multi-process: barriers between the phases, see :func:`_mh_barrier`).

    One ``ckpt.write`` span: chunks are made into bytes, hashed and written
    one at a time, so the seconds of the first two are an attr of the span
    (``serialize_s``) and not a span of their own."""
    from distributed_machine_learning_tpu import obs

    with obs.span("ckpt.write") as write_span:
        nbytes, nchunks, serialize_s = _write_generation(
            path, skeleton, leaves
        )
        write_span.set("bytes", nbytes).set("chunks", nchunks).set(
            "serialize_s", round(serialize_s, 6)
        )
    return nbytes, nchunks


def _write_generation(path: str, skeleton,
                      leaves: List[Any]) -> Tuple[int, int, float]:
    backend, p = get_storage(path)
    # Re-saving over a previous attempt at the same step: drop its COMMIT
    # FIRST so no reader ever pairs the old marker with new bytes.
    backend.delete(backend.join(p, COMMIT_NAME))
    _mh_barrier(f"ckpt_clear:{p}")
    cas = _cas_for(p)
    # Pin-then-scan GC contract: every digest is pinned the moment it is
    # published, and the pin is dropped only after the ref (and COMMIT)
    # landed — a concurrent sweep can never collect an in-flight save.
    pin = cas.pin() if cas is not None else None
    gen_digests: List[str] = []
    total_bytes = 0
    total_chunks = 0
    serialize_s = 0.0
    index_leaves: List[Dict[str, Any]] = []
    try:
        for n, leaf in enumerate(leaves):
            if not isinstance(leaf, HostLeaf):
                index_leaves.append({"literal": True, "value": leaf})
                continue
            chunk_recs = []
            for start, stop, arr in leaf.chunks:
                t0 = time.perf_counter()
                contiguous = np.ascontiguousarray(arr)
                data = contiguous.tobytes()
                fname = _chunk_file_name(n, start)
                rec = {
                    "file": fname,
                    "start": list(start),
                    "stop": list(stop),
                    "nbytes": len(data),
                    "sha256": hashlib.sha256(data).hexdigest(),
                }
                serialize_s += time.perf_counter() - t0
                if cas is not None:
                    blob_recs = []
                    for off, ln in store_lib.split_row_aligned(
                        len(data), _row_stride(contiguous)
                    ):
                        digest = cas.put_blob(data[off:off + ln])
                        pin.add(digest)
                        gen_digests.append(digest)
                        blob_recs.append({"h": digest, "nbytes": ln})
                    rec["blobs"] = blob_recs
                else:
                    backend.write_bytes(backend.join(p, fname), data)
                chunk_recs.append(rec)
                total_bytes += len(data)
                total_chunks += 1
            for start, stop in leaf.remote_chunks:  # pragma: no cover - multihost
                chunk_recs.append({
                    "file": _chunk_file_name(n, start),
                    "start": list(start),
                    "stop": list(stop),
                    "nbytes": None,
                    "sha256": None,
                })
            rec = {
                "shape": list(leaf.shape),
                "dtype": leaf.dtype,
                "chunks": chunk_recs,
            }
            if leaf.partition is not None:
                rec["partition"] = leaf.partition
            index_leaves.append(rec)
        # All processes' chunks must be on storage before the index/COMMIT
        # that names them (no-op single-process).
        _mh_barrier(f"ckpt_chunks:{p}")
        try:
            import jax

            process_index = jax.process_index()
        except Exception:  # pragma: no cover - pre-init
            process_index = 0
        if process_index == 0:
            try:
                import jax as _jax

                nproc = _jax.process_count()
            except Exception:  # pragma: no cover - pre-init
                nproc = 1
            index = {
                "format_version": FORMAT_VERSION,
                "tree": skeleton,
                "leaves": index_leaves,
                # Saving-side process layout: consumers (serve/export.py's
                # manifest topology block) can name the training topology
                # without probing chunk files.
                "process_count": nproc,
            }
            if cas is not None:
                # GC root BEFORE visibility: the ref lands ahead of the
                # index/COMMIT so a committed generation is always
                # reachable, while a save that dies here leaves only an
                # unreferenced ref + pinned-then-released blobs — plain
                # GC food, invisible to readers.
                manifest_digest = cas.put_manifest({
                    "kind": "ckpt-generation",
                    "path": p,
                    store_lib.MANIFEST_CHUNKS_KEY: sorted(set(gen_digests)),
                })
                pin.add(manifest_digest)
                cas.set_ref(
                    store_lib.ref_name_for_path("ckpt", p),
                    manifest_digest,
                    meta={"path": p, "kind": "ckpt-generation"},
                )
                index["store"] = {"root": cas.root, "version": 1}
            index_bytes = json.dumps(index, sort_keys=True).encode()
            backend.write_bytes(backend.join(p, INDEX_NAME), index_bytes)
            total_bytes += len(index_bytes)
            commit = {
                "index_sha256": hashlib.sha256(index_bytes).hexdigest(),
                "chunks": total_chunks,
                "bytes": total_bytes,
            }
            backend.write_bytes(
                backend.join(p, COMMIT_NAME), json.dumps(commit).encode()
            )
    finally:
        if pin is not None:
            pin.release()
    return total_bytes, total_chunks, serialize_s


def save_sharded(path: str, tree) -> str:
    """Snapshot + write ``tree`` as a committed generation at ``path``."""
    from distributed_machine_learning_tpu import obs

    t0 = time.time()
    with obs.span("ckpt.device_get"):
        skeleton, leaves = snapshot_tree(tree)
    nbytes, nchunks = write_snapshot(path, skeleton, leaves)
    get_metrics().record_save(time.time() - t0, nbytes, max(nchunks, 1))
    return path


# -- read ----------------------------------------------------------------------


def read_index(path: str, verify: bool = True) -> Optional[Dict[str, Any]]:
    """The parsed index of a COMMITTED generation; None when nothing is
    there at all; :class:`CheckpointCorruptionError` for a torn or damaged
    one (missing/invalid COMMIT, checksum mismatch, undecodable JSON)."""
    backend, p = get_storage(path)
    index_raw = backend.read_bytes(backend.join(p, INDEX_NAME))
    commit_raw = backend.read_bytes(backend.join(p, COMMIT_NAME))
    if index_raw is None and commit_raw is None:
        return None
    if commit_raw is None:
        raise CheckpointCorruptionError(
            f"uncommitted generation at {path} (no {COMMIT_NAME} marker — "
            f"the save never finished)"
        )
    if index_raw is None:
        raise CheckpointCorruptionError(
            f"generation at {path} has a {COMMIT_NAME} but no {INDEX_NAME}"
        )
    if verify:
        try:
            expected = json.loads(commit_raw).get("index_sha256")
        except ValueError as exc:
            raise CheckpointCorruptionError(
                f"undecodable {COMMIT_NAME} at {path}: {exc!r}"
            ) from exc
        if expected != hashlib.sha256(index_raw).hexdigest():
            raise CheckpointCorruptionError(
                f"index checksum mismatch at {path}"
            )
    try:
        return json.loads(index_raw)
    except ValueError as exc:
        raise CheckpointCorruptionError(
            f"undecodable {INDEX_NAME} at {path}: {exc!r}"
        ) from exc


def is_committed(path: str) -> bool:
    try:
        return read_index(path) is not None
    except CheckpointCorruptionError:
        return False


class _ChunkReader:
    """Lazy, cached, checksum-verifying chunk access for one generation —
    a restore touches only the chunk payloads its target sharding needs
    (``*.chunk`` files, or the content-store blobs a CAS-mode chunk
    record names — never both)."""

    def __init__(self, path: str, verify: bool = True,
                 store_root: Optional[str] = None):
        self.backend, self.base = get_storage(path)
        self.verify = verify
        self._cache: Dict[str, np.ndarray] = {}
        self.bytes_read = 0
        self._store = (
            store_lib.get_store(store_root) if store_root else None
        )

    def _chunk_bytes(self, rec: Dict[str, Any], fname: str) -> bytes:
        blobs = rec.get("blobs")
        if blobs:
            if self._store is None:
                raise CheckpointCorruptionError(
                    f"chunk {fname} under {self.base} is stored as content "
                    f"blobs but the index names no store root"
                )
            pieces: List[bytes] = []
            for b in blobs:
                piece = self._store.get_blob(b["h"])
                if piece is None:
                    raise CheckpointCorruptionError(
                        f"missing blob {b['h'][:12]}... for chunk {fname} "
                        f"under {self.base} (store {self._store.root})"
                    )
                pieces.append(piece)
            return b"".join(pieces)
        data = self.backend.read_bytes(self.backend.join(self.base, fname))
        if data is None:
            raise CheckpointCorruptionError(
                f"missing chunk {fname} under {self.base}"
            )
        return data

    def chunk_array(self, rec: Dict[str, Any], dtype, shape) -> np.ndarray:
        fname = rec["file"]
        arr = self._cache.get(fname)
        if arr is not None:
            return arr
        data = self._chunk_bytes(rec, fname)
        self.bytes_read += len(data)
        if self.verify and rec.get("sha256") is not None:
            if hashlib.sha256(data).hexdigest() != rec["sha256"]:
                raise CheckpointCorruptionError(
                    f"chunk checksum mismatch: {fname} under {self.base}"
                )
        cshape = tuple(
            int(b) - int(a) for a, b in zip(rec["start"], rec["stop"])
        )
        expected = int(np.prod(cshape, dtype=np.int64)) * dtype.itemsize
        if len(data) != expected:
            raise CheckpointCorruptionError(
                f"chunk {fname} has {len(data)} bytes, expected {expected}"
            )
        arr = np.frombuffer(data, dtype=dtype).reshape(cshape)
        self._cache[fname] = arr
        return arr


def _assemble(
    leaf_rec: Dict[str, Any],
    reader: _ChunkReader,
    requested: Optional[Tuple[slice, ...]] = None,
) -> np.ndarray:
    """Materialize the ``requested`` global slice of one leaf (the whole
    array when None) from the chunks that intersect it."""
    shape = tuple(int(d) for d in leaf_rec["shape"])
    dtype = _np_dtype(leaf_rec["dtype"])
    if requested is None:
        req_start = tuple(0 for _ in shape)
        req_stop = shape
    else:
        req_start, req_stop = _norm_index(requested, shape)
    out_shape = tuple(b - a for a, b in zip(req_start, req_stop))
    out = np.empty(out_shape, dtype=dtype)
    filled = 0
    for rec in leaf_rec["chunks"]:
        c_start = tuple(int(v) for v in rec["start"])
        c_stop = tuple(int(v) for v in rec["stop"])
        i_start = tuple(max(a, b) for a, b in zip(req_start, c_start))
        i_stop = tuple(min(a, b) for a, b in zip(req_stop, c_stop))
        if any(a >= b for a, b in zip(i_start, i_stop)):
            continue  # disjoint: this chunk is never read
        chunk = reader.chunk_array(rec, dtype, shape)
        out_sl = tuple(
            slice(a - r, b - r) for a, b, r in zip(i_start, i_stop, req_start)
        )
        in_sl = tuple(
            slice(a - c, b - c) for a, b, c in zip(i_start, i_stop, c_start)
        )
        out[out_sl] = chunk[in_sl]
        filled += int(np.prod(
            [b - a for a, b in zip(i_start, i_stop)], dtype=np.int64
        ))
    want = int(np.prod(out_shape, dtype=np.int64))
    if filled < want:
        raise CheckpointCorruptionError(
            f"chunk table does not cover the requested region "
            f"({filled}/{want} elements) for a leaf of shape {shape}"
        )
    return out


def _sharding_for(shardings, path_parts: Tuple[str, ...]):
    """Resolve the target sharding for one leaf: ``shardings`` is None, a
    callable ``('a','b','c') -> sharding|None``, or a nested pytree walked
    by the same keys as the checkpointed tree (missing entries -> None =
    plain numpy)."""
    if shardings is None:
        return None
    if callable(shardings):
        return shardings(path_parts)
    node = shardings
    for part in path_parts:
        if isinstance(node, dict):
            node = node.get(part)
        elif isinstance(node, (list, tuple)):
            try:
                node = node[int(part)]
            except (ValueError, IndexError):
                return None
        else:
            break
        if node is None:
            return None
    if isinstance(node, (dict, list, tuple)):
        return None
    return node


def load_sharded(
    path: str,
    verify: bool = True,
    shardings=None,
) -> Optional[Dict[str, Any]]:
    """Restore a generation.  Without ``shardings`` every array leaf is
    gathered to a full numpy array (the single-host/export path).  With
    ``shardings`` (see :func:`_sharding_for`) each array leaf becomes a
    ``jax.Array`` laid out for the TARGET mesh, built with
    ``jax.make_array_from_callback`` so only the chunks intersecting each
    local shard are ever read — the resharding-on-restore path.

    Returns None when nothing exists at ``path``; raises
    :class:`CheckpointCorruptionError` on torn/uncommitted/damaged data.
    """
    t0 = time.time()
    index = read_index(path, verify=verify)
    if index is None:
        return None
    reader = _ChunkReader(
        path, verify=verify,
        store_root=(index.get("store") or {}).get("root"),
    )
    leaves = index["leaves"]

    def rebuild(node, parts: Tuple[str, ...]):
        if isinstance(node, dict) and set(node) == {_LEAF_KEY}:
            rec = leaves[int(node[_LEAF_KEY])]
            if rec.get("literal"):
                return rec.get("value")
            sharding = _sharding_for(shardings, parts)
            if sharding is None:
                return _assemble(rec, reader)
            import jax

            shape = tuple(int(d) for d in rec["shape"])
            return jax.make_array_from_callback(
                shape, sharding, lambda idx, r=rec: _assemble(r, reader, idx)
            )
        if isinstance(node, dict):
            return {k: rebuild(v, parts + (k,)) for k, v in node.items()}
        if isinstance(node, list):
            return [
                rebuild(v, parts + (str(i),)) for i, v in enumerate(node)
            ]
        return node

    tree = rebuild(index["tree"], ())
    get_metrics().record_restore(time.time() - t0, reader.bytes_read)
    return tree


def list_files(path: str) -> List[str]:
    """Names of every file belonging to a generation (for deletion)."""
    backend, p = get_storage(path)
    return backend.listdir(p)


def delete_generation(path: str) -> int:
    """Remove a generation directory and everything in it (COMMIT first, so
    a reader racing the delete sees 'uncommitted', never 'torn'), then its
    content-store ref — a deleted generation whose ref lingered would
    retain its blobs forever (the ``gc_retained`` ref-leak runbook
    signal).  Returns the number of files removed."""
    backend, p = get_storage(path)
    recorded_root = None
    index_raw = backend.read_bytes(backend.join(p, INDEX_NAME))
    if index_raw is not None:
        try:
            recorded_root = (
                json.loads(index_raw).get("store") or {}
            ).get("root")
        except ValueError:
            recorded_root = None
    names = backend.listdir(p)
    ordered = sorted(names, key=lambda n: (n != COMMIT_NAME, n))
    removed = 0
    for name in ordered:
        backend.delete(backend.join(p, name))
        removed += 1
    import os

    if os.path.isdir(p):  # local scheme: clear the now-empty directory
        try:
            os.rmdir(p)
        except OSError:
            pass
    _drop_store_ref(p, recorded_root)
    return removed


def _drop_store_ref(path: str, recorded_root: Optional[str]) -> None:
    """Best-effort: delete the ``ckpt-*`` ref a generation at ``path``
    registered.  Tries the root its index recorded, then the default root
    for the path (a pre-index failure can leave a ref with no index)."""
    roots: List[str] = []
    if recorded_root:
        roots.append(recorded_root)
    try:
        fallback = store_lib.store_root_for(path)
        if fallback not in roots:
            roots.append(fallback)
    except Exception:  # noqa: BLE001 - ref cleanup must never fail a delete
        pass
    name = store_lib.ref_name_for_path("ckpt", path)
    for root in roots:
        try:
            cas = store_lib.get_store(root)
            if cas.read_ref(name) is not None:
                cas.delete_ref(name)
        except Exception:  # noqa: BLE001 - ref cleanup must never fail a delete
            continue


class _NotRefCopyable(Exception):
    """Internal: the source generation has chunk payloads outside the
    content store (legacy layout / multihost save)."""


def ref_copy_subtree(
    src_path: str,
    dst_path: str,
    keys: Sequence[str] = ("params", "batch_stats"),
) -> Optional[Dict[str, Any]]:
    """Publish a COMMITTED generation at ``dst_path`` whose chunk table
    names the SAME content-store blobs as ``src_path``'s sub-tree under
    ``keys`` — a metadata-only export: zero chunk payload bytes move,
    only a new manifest, ref, index and COMMIT.

    Returns ``{"chunks", "bytes_logical", "store_root", "path"}`` on
    success; None when the source cannot be ref-copied (legacy chunk-file
    layout, no store record, or no ``params`` sub-tree) — callers fall
    back to the load-and-reserialize path.  Raises
    :class:`CheckpointCorruptionError` when the source is torn or its
    blobs are missing (a ref-copy must never publish dangling digests).

    The destination registers its OWN ref in the SOURCE's store, so
    pruning the source generation later cannot strand the export: GC
    walks the destination's manifest and retains every shared blob.
    """
    index = read_index(src_path)
    if index is None:
        return None
    root = (index.get("store") or {}).get("root")
    if not root:
        return None
    tree = index.get("tree")
    if not isinstance(tree, dict):
        return None
    sub = {k: tree[k] for k in keys if k in tree}
    if "params" not in sub:
        return None
    src_leaves = index["leaves"]
    new_leaves: List[Dict[str, Any]] = []
    digests: List[str] = []
    bytes_logical = 0
    nchunks = 0

    def renumber(node):
        nonlocal bytes_logical, nchunks
        if isinstance(node, dict) and set(node) == {_LEAF_KEY}:
            rec = src_leaves[int(node[_LEAF_KEY])]
            if not rec.get("literal"):
                for chunk in rec["chunks"]:
                    blobs = chunk.get("blobs")
                    if not blobs:
                        raise _NotRefCopyable()
                    digests.extend(b["h"] for b in blobs)
                    bytes_logical += int(chunk.get("nbytes") or 0)
                    nchunks += 1
            new_leaves.append(rec)
            return {_LEAF_KEY: len(new_leaves) - 1}
        if isinstance(node, dict):
            return {k: renumber(v) for k, v in node.items()}
        if isinstance(node, list):
            return [renumber(v) for v in node]
        return node

    try:
        new_tree = renumber(sub)
    except _NotRefCopyable:
        return None

    cas = store_lib.get_store(root)
    unique = sorted(set(digests))
    missing = [d for d in unique if not cas.has_blob(d)]
    if missing:
        raise CheckpointCorruptionError(
            f"ref-copy source {src_path} names {len(missing)} missing "
            f"blob(s) under {root} (first: {missing[0][:12]}...)"
        )
    backend, dst = get_storage(dst_path)
    backend.delete(backend.join(dst, COMMIT_NAME))
    with cas.pin() as pin:
        for d in unique:
            pin.add(d)
        manifest_digest = cas.put_manifest({
            "kind": "ckpt-refcopy",
            "path": dst,
            "source": get_storage(src_path)[1],
            store_lib.MANIFEST_CHUNKS_KEY: unique,
        })
        pin.add(manifest_digest)
        cas.set_ref(
            store_lib.ref_name_for_path("ckpt", dst),
            manifest_digest,
            meta={"path": dst, "kind": "ckpt-refcopy"},
        )
        new_index = {
            "format_version": FORMAT_VERSION,
            "tree": new_tree,
            "leaves": new_leaves,
            "process_count": 1,
            "store": {"root": root, "version": 1},
        }
        index_bytes = json.dumps(new_index, sort_keys=True).encode()
        backend.write_bytes(backend.join(dst, INDEX_NAME), index_bytes)
        commit = {
            "index_sha256": hashlib.sha256(index_bytes).hexdigest(),
            "chunks": nchunks,
            "bytes": bytes_logical + len(index_bytes),
        }
        backend.write_bytes(
            backend.join(dst, COMMIT_NAME), json.dumps(commit).encode()
        )
    store_lib.get_metrics().add("ref_copies", nchunks)
    return {
        "chunks": nchunks,
        "bytes_logical": bytes_logical,
        "store_root": root,
        "path": dst,
    }


def saved_partition_specs(path: str) -> Optional[Dict[str, Any]]:
    """The rule-derived layout a generation was SAVED under: a pytree (same
    skeleton as the checkpoint) of ``jax.sharding.PartitionSpec`` for every
    leaf that recorded one (None for host/replicated leaves), plus the
    saving mesh's axis sizes under the ``"__mesh__"`` key of the returned
    dict.  Returns None for uncommitted/absent generations.

    This is what lets a restore re-derive NamedShardings on a NEW mesh
    from the same specs (``load_sharded(shardings=...)``) without
    re-resolving the rule table that produced them."""
    index = read_index(path)
    if index is None:
        return None
    from distributed_machine_learning_tpu.parallel.partition import (
        spec_from_jsonable,
    )

    leaves = index["leaves"]
    mesh_axes: Dict[str, int] = {}

    def rebuild(node):
        if isinstance(node, dict) and set(node) == {_LEAF_KEY}:
            rec = leaves[int(node[_LEAF_KEY])]
            part = rec.get("partition")
            if not part:
                return None
            for k, v in (part.get("mesh") or {}).items():
                mesh_axes.setdefault(str(k), int(v))
            return spec_from_jsonable(part.get("spec"))
        if isinstance(node, dict):
            return {k: rebuild(v) for k, v in node.items()}
        if isinstance(node, list):
            return [rebuild(v) for v in node]
        return None

    tree = rebuild(index["tree"])
    return {"specs": tree, "__mesh__": mesh_axes}

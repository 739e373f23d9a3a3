"""Checkpoint round-trip tests, including real optax optimizer state."""

import gc
import hashlib
import json
import os
import threading
import time
import tracemalloc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_machine_learning_tpu.tune.checkpoint import (
    load_checkpoint,
    restore_into,
    save_checkpoint,
)


def test_roundtrip_nested_pytree(tmp_path):
    tree = {
        "params": {"dense": {"kernel": np.arange(6.0).reshape(2, 3),
                             "bias": np.zeros(3)}},
        "epoch": 4,
    }
    path = str(tmp_path / "ck" / "c.msgpack")
    save_checkpoint(path, tree)
    raw = load_checkpoint(path)
    restored = restore_into(tree, raw)
    np.testing.assert_array_equal(restored["params"]["dense"]["kernel"],
                                  tree["params"]["dense"]["kernel"])
    assert int(restored["epoch"]) == 4


def test_roundtrip_optax_state(tmp_path):
    params = {"w": jnp.ones((3, 2)), "b": jnp.zeros(2)}
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    opt_state = tx.init(params)
    # take one real update so the state is non-trivial
    grads = jax.tree.map(jnp.ones_like, params)
    updates, opt_state = tx.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)

    path = str(tmp_path / "opt.msgpack")
    save_checkpoint(path, {"params": params, "opt_state": opt_state, "epoch": 0})
    raw = load_checkpoint(path)

    fresh_state = tx.init(jax.tree.map(jnp.zeros_like, params))
    template = {"params": jax.tree.map(jnp.zeros_like, params),
                "opt_state": fresh_state, "epoch": 0}
    restored = restore_into(template, raw)

    # restored opt state drives identical updates to the original
    u1, _ = tx.update(grads, restored["opt_state"], restored["params"])
    u2, _ = tx.update(grads, opt_state, params)
    for a, b in zip(jax.tree.leaves(u1), jax.tree.leaves(u2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_load_missing_returns_none(tmp_path):
    assert load_checkpoint(str(tmp_path / "nope.msgpack")) is None
    assert load_checkpoint(None) is None


def test_atomic_write_no_partial_files(tmp_path):
    path = str(tmp_path / "a" / "c.msgpack")
    save_checkpoint(path, {"x": np.ones(4)})
    save_checkpoint(path, {"x": np.zeros(4)})  # overwrite in place
    raw = load_checkpoint(path)
    np.testing.assert_array_equal(raw["x"], np.zeros(4))
    leftovers = [p for p in (tmp_path / "a").iterdir() if p.suffix == ".tmp"]
    assert not leftovers


# -- the streamed msgpack save: flax's bytes, from the leaves to the file ------


def _parents_bytes(tree):
    """What ``save_checkpoint`` wrote before it streamed: the tree read to
    the host, then flax's ``to_bytes`` of all of it."""
    from flax import serialization

    return serialization.to_bytes(jax.tree.map(
        lambda x: np.asarray(x) if hasattr(x, "shape") else x, tree
    ))


def _streamable_trees():
    import ml_dtypes

    rng = np.random.default_rng(0)
    wide = rng.normal(size=(300, 70)).astype(np.float32)
    return {
        "nested": lambda: {
            "b": [np.arange(5), (1, {"deep": [np.ones(2)]})],
            "a": {"z": (np.zeros(3), [4, 5]), "y": []},
        },
        "empty_dict": lambda: {},
        "keys_16": lambda: {f"k{i}": i for i in range(16)},
        "keys_70000": lambda: {f"k{i}": i for i in range(70000)},
        "float32": lambda: {"w": wide, "none": np.zeros((0, 3), np.float32)},
        "bfloat16": lambda: {
            "w": np.arange(40000).astype(ml_dtypes.bfloat16).reshape(200, 200),
            "small": np.ones(3, ml_dtypes.bfloat16),
        },
        "int8_bool": lambda: {
            "i": np.arange(-100, 100, dtype=np.int8),
            "fixext16": np.arange(6, dtype=np.int8),
            "b": rng.random(70000) > 0.5,
        },
        "zero_d": lambda: {"s": np.array(2.5, np.float32),
                           "i": np.array(7)},
        "numpy_scalars": lambda: {"f": np.float32(1.5), "i": np.int64(-3),
                                  "b": np.bool_(True), "d": np.float64(2.0)},
        "python_scalars": lambda: {
            "i": 12, "neg": -(2 ** 40), "f": 0.25, "s": "text", "n": None,
            "t": True, "c": 1.5 - 2j,
        },
        "non_contiguous": lambda: {
            "t": wide.T, "strided": np.arange(100000)[::3],
            "fortran": np.asfortranarray(wide),
        },
        "jax_arrays": lambda: {
            "p": jnp.arange(70000, dtype=jnp.float32).reshape(7, 10000),
            "q": jnp.ones((3, 3), jnp.bfloat16), "s": jnp.float32(2),
        },
        "over_chunk_size": lambda: {
            "big": np.arange(1000, dtype=np.float32).reshape(10, 100),
            "big_t": np.arange(2000, dtype=np.float32).reshape(40, 50).T,
            "big_dev": jnp.arange(999, dtype=jnp.bfloat16),
            "small": np.arange(3),
        },
    }


def _spans_of(fn):
    """Run ``fn`` under a tracer of its own; the span records it left."""
    from distributed_machine_learning_tpu import obs

    tracer = obs.Tracer()
    obs.install_tracer(tracer)
    try:
        fn()
    finally:
        obs.install_tracer(None)
    return tracer.records()


def _assert_trees_equal(got, want):
    assert type(got) is type(want) or not isinstance(want, dict)
    if isinstance(want, dict):
        assert list(got) == list(want)
        for key in want:
            _assert_trees_equal(got[key], want[key])
    elif hasattr(want, "shape"):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    else:
        assert got == want


@pytest.mark.parametrize("case", sorted(_streamable_trees()))
def test_streamed_save_writes_flax_bytes(tmp_path, monkeypatch, case):
    """The file is ``to_bytes`` of the host tree to the byte, the manifest
    its sha256 and length, and both load as a blob flax wrote does."""
    from flax import serialization

    from distributed_machine_learning_tpu.tune.checkpoint import (
        manifest_path_for,
    )

    if case == "over_chunk_size":
        monkeypatch.setattr(serialization, "MAX_CHUNK_SIZE", 1000)
    tree = _streamable_trees()[case]()
    want = _parents_bytes(tree)
    path = str(tmp_path / "c.msgpack")
    spans = _spans_of(lambda: save_checkpoint(path, tree))
    with open(path, "rb") as f:
        assert f.read() == want
    with open(manifest_path_for(path)) as f:
        assert json.load(f) == {
            "sha256": hashlib.sha256(want).hexdigest(),
            "bytes": len(want), "format": "flax-msgpack",
        }
    (save,) = [sp for sp in spans if sp["name"] == "ckpt.save"]
    assert save["args"]["streamed"] is True
    assert save["args"]["bytes"] == len(want)
    loaded = load_checkpoint(path)
    flax_path = str(tmp_path / "flax.msgpack")
    with open(flax_path, "wb") as f:  # a checkpoint from before this road
        f.write(want)
    _assert_trees_equal(loaded, load_checkpoint(flax_path))
    _assert_trees_equal(loaded, serialization.msgpack_restore(want))


@pytest.mark.parametrize("n", [0, 1, 6, 200, 65500, 65536])
def test_streamed_array_headers_at_msgpack_size_classes(n):
    """fixext, ext8/16/32 and bin8/16/32 each where msgpack puts them."""
    from flax import serialization

    from distributed_machine_learning_tpu.tune.checkpoint import (
        _PayloadStream,
    )

    for dtype in (np.int8, np.float32):
        for size in range(n, n + 40):
            tree = {"a": np.arange(size).astype(dtype)}
            got = b"".join(bytes(c) for c in _PayloadStream(tree))
            assert got == serialization.to_bytes(tree), (dtype, size)


def test_save_reports_which_road_a_tree_took(tmp_path):
    """``streamed`` on ``ckpt.save`` and ``saves_streamed``: a tree with a
    leaf the streamer does not cover is packed by flax, whole, to the same
    bytes it always was."""
    from distributed_machine_learning_tpu.ckpt.metrics import get_metrics

    trees = [({"w": jnp.ones(5), "epoch": 3}, True),
             ({"w": jnp.ones(5), "blob": b"raw bytes"}, False)]

    def save_both():
        for i, (tree, streamed) in enumerate(trees):
            before = get_metrics().snapshot()
            path = str(tmp_path / f"c{i}.msgpack")
            save_checkpoint(path, tree)
            delta = get_metrics().delta_since(before)
            assert delta["saves"] == 1
            assert delta["saves_streamed"] == int(streamed)
            with open(path, "rb") as f:
                assert f.read() == _parents_bytes(tree)
            assert load_checkpoint(path)["w"].shape == (5,)

    spans = _spans_of(save_both)
    saves = [sp for sp in spans if sp["name"] == "ckpt.save"]
    assert [sp["args"]["streamed"] for sp in saves] == [True, False]
    assert [sp["args"]["format"] for sp in saves] == ["msgpack", "msgpack"]
    writes = [sp for sp in spans if sp["name"] == "ckpt.write"]
    assert [sp["args"]["parent_id"] for sp in writes] == [
        sp["args"]["span_id"] for sp in saves
    ]
    for sp in writes:
        assert {"bytes", "chunks", "serialize_s"} <= set(sp["args"])
    # One read a device leaf inside the streamed write, when its turn
    # comes; on flax's road one read of the whole tree before it is packed.
    reads = [sp for sp in spans if sp["name"] == "ckpt.device_get"]
    assert [sp["args"]["parent_id"] for sp in reads] == [
        sp["args"]["span_id"] for sp in writes
    ]
    assert not [sp for sp in spans if sp["name"] == "ckpt.serialize"]


def test_failed_chunk_leaves_nothing_and_surfaces_on_wait(tmp_path):
    """A backend that fails at its n-th chunk: no payload under the final
    name, no manifest, no temporary file; the writer raises on ``wait``."""
    from distributed_machine_learning_tpu.tune import storage as storage_lib
    from distributed_machine_learning_tpu.tune.checkpoint import (
        AsyncCheckpointWriter,
    )

    class FailsAtThirdChunk(storage_lib.LocalStorage):
        def write_chunks(self, path, chunks):
            def failing():
                for n, chunk in enumerate(chunks, start=1):
                    if n == 3:
                        raise RuntimeError("disk gone at chunk 3")
                    yield chunk

            return super().write_chunks(path, failing())

    tree = {f"w{i}": np.full(40000, i, np.float32) for i in range(4)}
    storage_lib.set_fault_wrapper(lambda backend: FailsAtThirdChunk())
    writer = AsyncCheckpointWriter(log=lambda m: None)
    try:
        path = writer.submit(str(tmp_path / "ck" / "c.msgpack"), tree)
        with pytest.raises(RuntimeError, match="disk gone at chunk 3"):
            writer.wait(path, timeout=30)
    finally:
        storage_lib.set_fault_wrapper(None)
        writer.close()
    assert os.listdir(tmp_path / "ck") == []
    save_checkpoint(path, tree)  # the same path takes a sound save after
    assert sorted(os.listdir(tmp_path / "ck")) == [
        "c.msgpack", "c.msgpack.manifest.json"
    ]


def test_retried_streamed_write_hashes_the_pass_that_landed(tmp_path):
    """A transient fault part-way: the retry layer iterates the stream
    again from its start, and the manifest is of the bytes on storage."""
    from distributed_machine_learning_tpu.tune import storage as storage_lib

    attempts = []

    class FlakyOnce(storage_lib.LocalStorage):
        def write_chunks(self, path, chunks):
            def flaky():
                for n, chunk in enumerate(chunks, start=1):
                    if n == 3 and len(attempts) == 1:
                        raise OSError("transient")
                    yield chunk

            attempts.append(path)
            return super().write_chunks(path, flaky())

    tree = {f"w{i}": np.full(40000, i, np.float32) for i in range(4)}
    path = str(tmp_path / "c.msgpack")
    storage_lib.set_fault_wrapper(lambda backend: FlakyOnce())
    try:
        save_checkpoint(path, tree)
    finally:
        storage_lib.set_fault_wrapper(None)
    assert attempts == [path, path, path + ".manifest.json"]
    with open(path, "rb") as f:
        assert f.read() == _parents_bytes(tree)
    assert load_checkpoint(path, verify=True)["w3"][0] == 3.0


@pytest.mark.parametrize("leaves", ["host", "device"])
def test_save_lets_go_of_the_tree_without_the_cycle_collector(
        tmp_path, monkeypatch, leaves):
    """A snapshot is a second copy of the state on the device: once it is
    written, reference counts alone must free it (a planner made of
    recursive closures, a reference cycle, kept five of them alive on a chip, and the next
    epoch's program no longer fitted).  ``device``: the same of a snapshot
    that one dispatch made, which no cache of the copy program may hold."""
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    tree = {"w": np.ones(70000, np.float32), "d": jnp.ones((300, 300)),
            "n": {"b": np.ones(3)}}
    alive = [weakref.ref(x) for x in jax.tree.leaves(tree)]
    writer = cl.AsyncCheckpointWriter(log=lambda m: None)
    gc.collect()
    gc.disable()
    try:
        save_checkpoint(str(tmp_path / "sync.msgpack"), tree)
        del tree
        assert [ref() for ref in alive] == [None] * 3
        # ... and the writer's thread does not sit on the last snapshot
        # while its queue is empty.
        snapshots = []
        real = cl.save_checkpoint

        def noting(path, snapshot):
            snapshots.extend(
                weakref.ref(x) for x in jax.tree.leaves(snapshot)
            )
            return real(path, snapshot)

        monkeypatch.setattr(cl, "save_checkpoint", noting)
        state = ({"w": np.ones(70000, np.float32)} if leaves == "host" else
                 {"w": jnp.ones(70000), "m": {"v": jnp.ones((30, 30))}})
        originals = [weakref.ref(x) for x in jax.tree.leaves(state)]
        path = writer.submit(str(tmp_path / "async.msgpack"), state)
        assert writer.wait(path, timeout=30)
        deadline = time.monotonic() + 10
        while (any(ref() is not None for ref in snapshots)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert snapshots and [ref() for ref in snapshots] == (
            [None] * len(snapshots)
        )
        # The snapshot was another object than the state, which is the
        # caller's to drop.
        assert all(ref() is not None for ref in originals)
        del state
        assert [ref() for ref in originals] == [None] * len(originals)
    finally:
        gc.enable()
        writer.close()


# -- the snapshot: one dispatch a device set -----------------------------------


def _mesh_2x4():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))


def _snapshot_cases():
    """name -> (a maker of the tree, the checkpoint's file name)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def single():
        return {"params": {"w": jnp.arange(70000, dtype=jnp.float32),
                           "b": jnp.ones((2, 3), jnp.bfloat16)},
                "opt": {"count": jnp.int32(3)}, "epoch": 1}

    def over_mesh():
        mesh = _mesh_2x4()
        specs = {"rep": P(), "dp": P("dp"), "dp_none": P("dp", None),
                 "tp": P(None, "tp"), "both": P("dp", "tp"),
                 "joined": P(("dp", "tp"))}
        tree = {
            name: jax.device_put(
                jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8) + i,
                NamedSharding(mesh, spec),
            )
            for i, (name, spec) in enumerate(specs.items())
        }
        tree["count"] = jax.device_put(
            jnp.int32(7), NamedSharding(mesh, P())
        )
        return {"params": tree, "epoch": 2}

    def mixed():
        devices = jax.devices()
        return {
            "on0": jax.device_put(jnp.arange(5000.0), devices[0]),
            "on3": {"w": jax.device_put(jnp.ones((40, 40)), devices[3]),
                    "v": jax.device_put(jnp.zeros(7, jnp.int8), devices[3])},
            "host": {"buf": np.arange(6, dtype=np.float32),
                     "big": np.full(70000, 2.0, np.float32)},
            "epoch": 4, "lr": 0.5, "rng_impl": "threefry", "none": None,
        }

    return {
        "single-msgpack": (single, "ckpt_000001.msgpack"),
        "mesh-msgpack": (over_mesh, "ckpt_000001.msgpack"),
        "mesh-sharded": (over_mesh, "gen_000001"),
        "mixed-msgpack": (mixed, "ckpt_000001.msgpack"),
    }


def _buffer_pointers(x):
    return [sh.data.unsafe_buffer_pointer() for sh in x.addressable_shards]


def _submit_noting_snapshot(tmp_path, monkeypatch, case):
    """Submit the case's tree as a trial does (the originals deleted right
    after, as the next step's donation deletes them; numpy leaves written
    into), wait, and hand back what the tree held before, what the
    writer's thread was given (per device leaf: buffer pointers and
    sharding; nothing that keeps the snapshot alive) and what the file
    reads back as."""
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    make, name = _snapshot_cases()[case]
    tree = make()
    leaves = jax.tree.leaves(tree, is_leaf=lambda x: x is None)
    before = {
        "host": jax.tree.map(
            lambda x: np.array(x) if hasattr(x, "shape") else x, tree
        ),
        "device": [(_buffer_pointers(x), x.sharding) for x in leaves
                   if isinstance(x, jax.Array)],
    }
    given = []
    real = cl.save_checkpoint

    def noting(path, snapshot):
        given.extend(
            (_buffer_pointers(x), x.sharding)
            for x in jax.tree.leaves(snapshot) if isinstance(x, jax.Array)
        )
        return real(path, snapshot)

    monkeypatch.setattr(cl, "save_checkpoint", noting)
    writer = cl.AsyncCheckpointWriter(log=lambda m: None)
    try:
        path = writer.submit(str(tmp_path / name), tree)
        for x in leaves:
            if isinstance(x, jax.Array):
                x.delete()  # what donate_argnums does to the next step's
            elif isinstance(x, np.ndarray):
                x[...] = 99  # the trainable reuses its host buffers
        assert writer.wait(path, timeout=60)
    finally:
        writer.close()
    return before, given, load_checkpoint(path)


@pytest.mark.parametrize("case", sorted(_snapshot_cases()))
def test_snapshot_is_a_copy_that_outlives_donated_originals(
        tmp_path, monkeypatch, case):
    """No buffer of the snapshot is a buffer of the state, so the state's
    deletion (donation, stood in for by ``delete()``) and the caller's
    writes into its numpy leaves leave the file the tree as submitted."""
    before, given, loaded = _submit_noting_snapshot(
        tmp_path, monkeypatch, case
    )
    assert len(given) == len(before["device"]) > 0
    for (was, _), (now, _) in zip(before["device"], given):
        assert len(now) == len(was) and not set(now) & set(was)
    from flax import serialization

    _assert_trees_equal(loaded, serialization.to_state_dict(before["host"]))


@pytest.mark.parametrize("case", sorted(_snapshot_cases()))
def test_snapshot_leaf_keeps_its_originals_sharding(
        tmp_path, monkeypatch, case):
    """Exactly, a spec's trailing ``None`` included: a snapshot laid out
    otherwise would change the chunks the sharded format writes."""
    before, given, _ = _submit_noting_snapshot(tmp_path, monkeypatch, case)
    assert [s for _, s in given] == [s for _, s in before["device"]]


def test_snapshot_resharded_when_the_copy_comes_back_laid_out_otherwise(
        tmp_path, monkeypatch):
    """The copy program is free to choose its results' layout; what
    ``submit`` hands the writer is laid out as the state was."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_machine_learning_tpu.tune import checkpoint as cl

    mesh = _mesh_2x4()
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("dp", "tp")))
    monkeypatch.setattr(cl, "_copy_on_device", lambda leaves: [
        jax.device_put(leaf.copy(), NamedSharding(mesh, P()))
        for leaf in leaves
    ])
    given = []
    monkeypatch.setattr(cl, "save_checkpoint",
                        lambda path, tree: given.append(tree["x"].sharding))
    writer = cl.AsyncCheckpointWriter(log=lambda m: None)
    try:
        assert writer.wait(writer.submit(str(tmp_path / "gen_000001"),
                                         {"x": x}), timeout=30)
    finally:
        writer.close()
    assert given == [x.sharding]


@pytest.mark.parametrize("case,dispatches,to_host", [
    ("single-msgpack", 1, False),
    ("mesh-sharded", 1, False),
    ("mixed-msgpack", 2, False),
    ("host-leaves-only", 0, False),
    ("no-room-on-the-device", 0, True),
])
def test_snapshot_span_and_counters_say_how_many_dispatches(
        tmp_path, monkeypatch, case, dispatches, to_host):
    """``report.ckpt_snapshot`` carries ``leaves`` (every leaf of the tree)
    and ``dispatches`` (one a set of devices; none on the host road), and
    the checkpoint counters sum both."""
    from distributed_machine_learning_tpu.ckpt.metrics import get_metrics
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    if case == "host-leaves-only":
        tree, name = {"w": np.ones(5), "epoch": 3, "tag": "x"}, "c.msgpack"
    elif case == "no-room-on-the-device":
        tree, name = _snapshot_cases()["single-msgpack"][0](), "c.msgpack"
        monkeypatch.setattr(cl.AsyncCheckpointWriter, "_device_has_room_for",
                            staticmethod(lambda leaves: False))
    else:
        make, name = _snapshot_cases()[case]
        tree = make()
    n_leaves = len(jax.tree.leaves(tree))
    writer = cl.AsyncCheckpointWriter(log=lambda m: None)
    base = get_metrics().snapshot()

    def submit_twice():
        for i in (1, 2):
            path = writer.submit(str(tmp_path / str(i) / name), tree)
            assert writer.wait(path, timeout=60)

    try:
        spans = _spans_of(submit_twice)
    finally:
        writer.close()
    snaps = [sp for sp in spans if sp["name"] == "report.ckpt_snapshot"]
    assert [sp["args"]["leaves"] for sp in snaps] == [n_leaves] * 2
    assert [sp["args"]["dispatches"] for sp in snaps] == [dispatches] * 2
    assert [sp["args"].get("to_host", False) for sp in snaps] == [to_host] * 2
    delta = get_metrics().delta_since(base)
    assert delta["snapshot_dispatches"] == 2 * dispatches
    assert delta["snapshot_leaves"] == 2 * n_leaves


def test_snapshot_counters_reach_experiment_state_through_tune_run(tmp_path):
    from distributed_machine_learning_tpu import tune

    def trainable(config):
        w = jnp.zeros((4, 3))
        for epoch in range(3):
            w = w + 1
            tune.report(
                {"loss": 1.0 / (epoch + 1)},
                checkpoint={"params": {"w": w, "b": jnp.ones(3)},
                            "host": np.arange(4), "epoch": epoch},
            )

    tune.run(trainable, {"x": 1}, metric="loss", num_samples=1,
             storage_path=str(tmp_path), name="snap", verbose=0)
    with open(tmp_path / "snap" / "experiment_state.json") as f:
        counters = json.load(f)["checkpoint"]
    assert counters["snapshot_dispatches"] == 3
    assert counters["snapshot_leaves"] == 3 * 4
    assert counters["saves"] == 3
    tree = load_checkpoint(str(
        tmp_path / "snap" / "trial_00000" / "checkpoints"
        / "ckpt_000003.msgpack"
    ))
    np.testing.assert_array_equal(tree["params"]["w"], np.full((4, 3), 3.0))


def test_second_writer_finds_the_copy_program_compiled(tmp_path):
    """The copy program is the module's and jax keys it on the tree's
    shapes, dtypes and shardings: a new writer (each ``tune.run`` makes
    one) on a new state of the same structure traces and compiles nothing,
    which is what keeps a benchmark's window at no compiles after its
    set-up trial."""
    from distributed_machine_learning_tpu.compilecache import get_tracker
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    def state(fill):
        return {"params": {"w": jnp.full((33, 17), fill),
                           "b": jnp.full((17,), fill, jnp.bfloat16)},
                "count": jnp.int32(fill), "epoch": fill}

    tracker = get_tracker()

    def save_through_a_new_writer(i):
        writer = cl.AsyncCheckpointWriter(log=lambda m: None)
        try:
            path = writer.submit(str(tmp_path / f"ckpt_{i:06d}.msgpack"),
                                 state(float(i)))
            assert writer.wait(path, timeout=30)
        finally:
            writer.close()
        return path

    save_through_a_new_writer(1)
    before = tracker.snapshot()
    path = save_through_a_new_writer(2)
    after = tracker.snapshot()
    assert after["traces"] == before["traces"]
    assert after["backend_compiles"] == before["backend_compiles"]
    assert load_checkpoint(path)["params"]["w"][0, 0] == 2.0


def test_streamed_save_holds_one_leaf_not_the_payload(tmp_path):
    """The mechanism itself: a 64 MB tree of 8 MB leaves is saved with
    under two leaves' bytes of new host memory at the peak (packed whole it
    was the payload and a copy of every leaf: over 128 MB)."""
    leaf = 8 << 20
    tree = {f"w{i}": np.full(leaf // 4, i, np.float32) for i in range(8)}
    path = str(tmp_path / "c.msgpack")
    save_checkpoint(str(tmp_path / "warm.msgpack"), {"w": np.ones(3)})
    tracemalloc.start()
    try:
        start, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        save_checkpoint(path, tree)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak - start < 2 * leaf, peak - start
    assert os.path.getsize(path) > 8 * leaf


class TestAsyncCheckpointWriter:
    def test_submit_then_wait_round_trips(self, tmp_path):
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
            load_checkpoint,
        )

        w = AsyncCheckpointWriter()
        tree = {"params": {"w": np.arange(6, dtype=np.float32)}, "epoch": 3}
        path = str(tmp_path / "ckpt_000001.msgpack")
        w.submit(path, tree)
        w.wait(path)
        restored = load_checkpoint(path)
        np.testing.assert_array_equal(
            restored["params"]["w"], tree["params"]["w"]
        )
        assert restored["epoch"] == 3
        w.close()

    def test_mutating_numpy_leaf_after_submit_is_safe(self, tmp_path):
        """submit() snapshots mutable numpy leaves — later in-place writes by
        the caller must not leak into the checkpoint."""
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
            load_checkpoint,
        )

        w = AsyncCheckpointWriter()
        buf = np.zeros(4, dtype=np.float32)
        path = str(tmp_path / "ckpt_000001.msgpack")
        w.submit(path, {"buf": buf})
        buf[:] = 99.0  # trainable reuses its buffer for the next epoch
        w.wait(path)
        np.testing.assert_array_equal(
            load_checkpoint(path)["buf"], np.zeros(4, np.float32)
        )
        w.close()

    def test_wait_all_flushes_in_order(self, tmp_path):
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
            find_latest_checkpoint,
        )

        w = AsyncCheckpointWriter()
        for i in range(1, 6):
            w.submit(str(tmp_path / f"ckpt_{i:06d}.msgpack"), {"i": i})
        w.wait()
        path, it = find_latest_checkpoint(str(tmp_path))
        assert it == 5 and path.endswith("ckpt_000005.msgpack")
        w.close()

    def test_write_error_surfaces_on_wait_and_close(self, tmp_path):
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
        )

        w = AsyncCheckpointWriter()
        bad = str(tmp_path / "no_such_dir" / "sub" / "ckpt_000001.msgpack")
        # Local storage creates parents; force failure via an unserializable
        # leaf instead (msgpack rejects object dtype).
        w.submit(bad, {"x": np.array([object()])})
        with pytest.raises(Exception):
            w.wait(bad)
        w.close()  # errors already surfaced; close must not hang

    def test_wait_claims_error_once(self, tmp_path):
        """A raised write error is CLAIMED: later waits on the same path
        succeed instead of re-raising forever, and close() does not re-log
        it as 'never waited on' (advisor r3)."""
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
        )

        logged = []
        w = AsyncCheckpointWriter(log=logged.append)
        bad = str(tmp_path / "ckpt_000001.msgpack")
        w.submit(bad, {"x": np.array([object()])})
        with pytest.raises(Exception):
            w.wait(bad)
        assert w.wait(bad) is True  # claimed — no poison re-raise
        w.close()
        assert not any("failed" in m for m in logged), logged

    def test_waiting_unknown_path_is_noop(self):
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
        )

        w = AsyncCheckpointWriter()
        w.wait("/never/submitted")  # returns immediately, no error
        w.close()


    def test_survives_donated_source_buffers(self, tmp_path):
        """The TPU donation race (code review r3): the train step donates
        params/opt_state buffers, so the arrays submitted for writing get
        DELETED while the writer serializes. submit() must device-copy jax
        leaves; deleting the originals right after submit emulates donation
        deterministically."""
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
            load_checkpoint,
        )

        w = AsyncCheckpointWriter()
        params = {"w": jnp.arange(8, dtype=jnp.float32),
                  "b": jnp.ones((2, 3))}
        path = str(tmp_path / "ckpt_000001.msgpack")
        w.submit(path, {"params": params, "epoch": 1})
        for leaf in jax.tree_util.tree_leaves(params):
            leaf.delete()  # what donate_argnums does to the next step's args
        w.wait(path)
        restored = load_checkpoint(path)
        np.testing.assert_array_equal(
            restored["params"]["w"], np.arange(8, dtype=np.float32)
        )
        w.close()

    def test_close_logs_unclaimed_errors(self, tmp_path):
        from distributed_machine_learning_tpu.tune.checkpoint import (
            AsyncCheckpointWriter,
        )

        logged = []
        w = AsyncCheckpointWriter(log=logged.append)
        w.submit(str(tmp_path / "ckpt_000001.msgpack"),
                 {"x": np.array([object()])})  # unserializable -> write fails
        w.close()  # never waited on: close must LOG, not swallow
        assert any("failed" in m for m in logged), logged

    def test_close_timeout_abandons_hung_write(self, tmp_path, monkeypatch):
        from distributed_machine_learning_tpu.tune import checkpoint as cl

        logged = []
        slow = threading.Event()

        def hung_save(path, tree):
            slow.wait(30)  # simulates a stalled gs:// write

        monkeypatch.setattr(cl, "save_checkpoint", hung_save)
        w = cl.AsyncCheckpointWriter(log=logged.append)
        w.submit(str(tmp_path / "ckpt_000001.msgpack"), {"x": np.ones(2)})
        t0 = time.time()
        w.close(timeout=0.5)  # must return promptly, not block teardown
        assert time.time() - t0 < 5
        assert any("abandoning" in m for m in logged), logged
        slow.set()


def test_prune_keeps_durable_files_while_write_pending(tmp_path):
    """Retention with an async in-flight newest NEVER deletes the last
    ``keep`` durable files against it — the in-flight write may still fail
    (crash/preemption), and deleting first would leave zero restorable
    checkpoints (advisor r3, medium). The set is keep+1 transiently; the
    next prune (pending landed) converges to exactly keep."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        checkpoint_path,
        prune_checkpoints,
        save_checkpoint,
    )

    d = str(tmp_path)
    for i in range(1, 5):
        save_checkpoint(checkpoint_path(d, i), {"i": i})
    pending = checkpoint_path(d, 5)  # submitted, not yet written
    deleted = prune_checkpoints(d, keep=2, protect={pending},
                                pending_latest=pending)
    assert deleted == 2  # newest 2 DURABLE files (3, 4) survive
    import os as _os

    def _data_files():
        return sorted(p for p in _os.listdir(d) if p.endswith(".msgpack"))

    left = _data_files()
    assert left == ["ckpt_000003.msgpack", "ckpt_000004.msgpack"]
    # Integrity sidecars prune with their checkpoints: none orphaned.
    manifests = sorted(p for p in _os.listdir(d) if p.endswith(".json"))
    assert manifests == [f + ".manifest.json" for f in left]
    save_checkpoint(pending, {"i": 5})  # the write lands -> keep+1
    assert len(_data_files()) == 3
    # Next result's prune converges back to exactly keep.
    deleted = prune_checkpoints(d, keep=2, pending_latest=pending)
    assert deleted == 1
    assert _data_files() == [
        "ckpt_000004.msgpack", "ckpt_000005.msgpack"
    ]


def test_prune_keep_one_with_pending_preserves_durable(tmp_path):
    """keep_checkpoints_num=1 with the newest write still in flight: the
    newest DURABLE file must survive — a crash during the in-flight window
    must leave a restorable checkpoint (advisor r3, medium)."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        checkpoint_path,
        prune_checkpoints,
        save_checkpoint,
    )

    d = str(tmp_path)
    for i in range(1, 4):
        save_checkpoint(checkpoint_path(d, i), {"i": i})
    pending = checkpoint_path(d, 4)
    deleted = prune_checkpoints(d, keep=1, protect={pending},
                                pending_latest=pending)
    assert deleted == 2  # ckpt 3 survives as the durable restore point
    import os as _os

    def _data_files():
        return sorted(p for p in _os.listdir(d) if p.endswith(".msgpack"))

    assert _data_files() == ["ckpt_000003.msgpack"]
    save_checkpoint(pending, {"i": 4})
    deleted = prune_checkpoints(d, keep=1, pending_latest=pending)
    assert deleted == 1
    assert _data_files() == ["ckpt_000004.msgpack"]


def test_orbax_export_import_round_trip(tmp_path):
    """Interop bridge: framework msgpack checkpoint -> orbax
    StandardCheckpoint -> raw pytree, values intact (the hand-off path to
    orbax-consuming serving/fine-tuning stacks)."""
    pytest.importorskip("orbax.checkpoint")
    from distributed_machine_learning_tpu.tune.checkpoint import (
        checkpoint_path,
        export_orbax,
        import_orbax,
        save_checkpoint,
    )

    src = checkpoint_path(str(tmp_path / "ck"), 3)
    tree = {
        "params": {"dense": {"kernel": np.arange(6.0).reshape(2, 3),
                             "bias": np.zeros(3)}},
        "epoch": 3,
    }
    save_checkpoint(src, tree)
    out = export_orbax(src, str(tmp_path / "orbax_ck"))
    restored = import_orbax(out)
    np.testing.assert_array_equal(
        restored["params"]["dense"]["kernel"],
        tree["params"]["dense"]["kernel"],
    )
    assert int(restored["epoch"]) == 3

    with pytest.raises(FileNotFoundError):
        export_orbax(str(tmp_path / "nope.msgpack"), str(tmp_path / "x"))


def test_depth2_write_pipeline_overlaps_slow_write(tmp_path, monkeypatch):
    """Thread executor's checkpoint pipeline is depth 2: one slow write
    overlaps TWO epochs of training. The first write blocks on a gate the
    TRAINABLE releases only at epoch 3 — reaching epoch 3 proves epoch 2's
    report did not stall behind the in-flight write (depth 1 would sit in
    a 120s bounded wait instead)."""
    import threading as _threading
    import time as _time

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    gate = _threading.Event()
    progressed = []
    real_save = cl.save_checkpoint

    def gated_save(path, tree):
        if "ckpt_000001" in path:
            assert gate.wait(60), "gate never released"
        return real_save(path, tree)

    monkeypatch.setattr(cl, "save_checkpoint", gated_save)

    def trainable(config):
        for epoch in range(3):
            if epoch == 2:
                # Write 1 is still gated; getting here means report(1) and
                # report(2)'s submits didn't block behind it.
                progressed.append(not gate.is_set())
                gate.set()
            tune.report({"validation_loss": 1.0}, checkpoint={"e": epoch})

    t0 = _time.time()
    analysis = tune.run(
        trainable,
        {"num_epochs": 3},
        metric="validation_loss",
        num_samples=1,
        storage_path=str(tmp_path),
        keep_checkpoints_num=10,
        verbose=0,
    )
    assert progressed == [True]
    assert _time.time() - t0 < 60  # no 120s hung-write stall
    t = analysis.trials[0]
    assert t.latest_checkpoint and t.latest_checkpoint.endswith(
        "ckpt_000003.msgpack"
    )


def test_final_retention_converges_with_inflight_writes(tmp_path, monkeypatch):
    """keep_checkpoints_num=1 with slow writes: the runner's end-of-run
    retention pass (after the writer drains) leaves EXACTLY one file per
    trial — writes landing after a trial's last in-run prune must not
    inflate the on-disk set (code review r4)."""
    import os
    import time as _time

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import checkpoint as cl

    real_save = cl.save_checkpoint

    def slow_save(path, tree):
        _time.sleep(0.15)  # every write outlives its epoch
        return real_save(path, tree)

    monkeypatch.setattr(cl, "save_checkpoint", slow_save)

    def trainable(config):
        for epoch in range(4):
            tune.report({"validation_loss": 1.0}, checkpoint={"e": epoch})

    analysis = tune.run(
        trainable,
        {"num_epochs": 4},
        metric="validation_loss",
        num_samples=2,
        storage_path=str(tmp_path),
        keep_checkpoints_num=1,
        verbose=0,
    )
    for t in analysis.trials:
        d = os.path.dirname(t.latest_checkpoint)
        files = sorted(f for f in os.listdir(d) if f.endswith(".msgpack"))
        assert files == ["ckpt_000004.msgpack"], files


# ---------------------------------------------------------------------------
# At-least-once fencing: quarantine of unreported generations (ISSUE 7)


def _write_gens(tmp_path, steps, fmt="msgpack"):
    from distributed_machine_learning_tpu.tune.checkpoint import (
        checkpoint_path,
    )

    d = str(tmp_path / "trial_ckpts")
    paths = {}
    for s in steps:
        p = checkpoint_path(d, s, fmt)
        save_checkpoint(p, {"params": {"w": np.full(4, float(s))},
                            "epoch": s - 1})
        paths[s] = p
    return d, paths


@pytest.mark.parametrize("fmt", ["msgpack", "sharded"])
def test_quarantine_unreported_generations(tmp_path, fmt):
    """A fenced zombie's checkpoint (step > last reported) is renamed out
    of the generation namespace; the newest-valid walk then lands on the
    last REPORTED generation — the retry re-reports the fenced epoch
    instead of silently skipping it."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        newest_valid_checkpoint,
        quarantine_unreported,
    )

    d, _ = _write_gens(tmp_path, [1, 2, 3], fmt)
    # Driver processed 2 reports; the step-3 generation is the zombie's.
    path, it = newest_valid_checkpoint(d)
    assert it == 3  # without the guard, the requeue would restore this
    n = quarantine_unreported(d, 2, tag="i0", log=lambda m: None)
    assert n == 1
    path, it = newest_valid_checkpoint(d)
    assert it == 2
    tree = load_checkpoint(path)
    assert int(tree["epoch"]) == 1
    # The zombie's bytes survive for forensics, under the fenced prefix.
    import os

    fenced = [f for f in os.listdir(str(tmp_path / "trial_ckpts"))
              if f.startswith("fenced")]
    assert fenced, "quarantined generation should remain on storage"
    # A second quarantine pass is a no-op (idempotent at requeue time).
    assert quarantine_unreported(d, 2, tag="i1", log=lambda m: None) == 0


def test_newest_valid_checkpoint_max_iteration(tmp_path):
    """The max_iteration bound skips unreported generations even before
    (or racing) the quarantine rename."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        newest_valid_checkpoint,
    )

    d, _ = _write_gens(tmp_path, [1, 2, 4])
    path, it = newest_valid_checkpoint(d, max_iteration=3)
    assert it == 2
    path, it = newest_valid_checkpoint(d, max_iteration=0)
    assert path is None and it == 0


def test_quarantined_generations_invisible_to_fallback(tmp_path):
    """load_checkpoint_with_fallback (the worker-side corruption path)
    cannot rediscover a quarantined generation."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        load_checkpoint_with_fallback,
        quarantine_unreported,
    )

    d, paths = _write_gens(tmp_path, [1, 2, 3])
    quarantine_unreported(d, 1, log=lambda m: None)
    # Restore target itself was quarantined -> fallback walks the
    # remaining generations and lands on step 1, never 2 or 3.
    tree, used, it = load_checkpoint_with_fallback(paths[3], d,
                                                   log=lambda m: None)
    assert it == 1
    assert int(tree["epoch"]) == 0

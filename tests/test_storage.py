"""Pluggable checkpoint storage: backends, scheme dispatch, retention, and
end-to-end checkpoint/restore through ``tune.run`` against the in-memory fake.

Capability lineage: the reference persists only to a local ``local_dir``
(`/root/reference/ray-tune-hpo-regression.py:476`) and has no checkpointing at
all; BASELINE's north star requires checkpoint/restore of flax/optax pytrees
to shared (GCS) storage — this suite exercises that interface without a
network by swapping the backend via the path scheme.
"""

import numpy as np
import pytest

from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import dummy_regression_data
from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
from distributed_machine_learning_tpu.tune.experiment import ExperimentStore
from distributed_machine_learning_tpu.tune.storage import (
    LocalStorage,
    MemoryStorage,
    get_storage,
)
from distributed_machine_learning_tpu.tune.trial import Trial


@pytest.fixture(autouse=True)
def _fresh_memory():
    MemoryStorage.clear()
    yield
    MemoryStorage.clear()


def test_scheme_dispatch(tmp_path):
    # get_storage wraps every scheme backend with the retry layer; the
    # dispatched backend is the wrapper's inner.
    from distributed_machine_learning_tpu.tune.storage import RetryingStorage

    backend, p = get_storage(str(tmp_path / "x"))
    assert isinstance(backend, RetryingStorage)
    assert isinstance(backend.inner, LocalStorage) and p == str(tmp_path / "x")
    backend, p = get_storage("file://" + str(tmp_path / "y"))
    assert isinstance(backend.inner, LocalStorage) and p == str(tmp_path / "y")
    backend, p = get_storage("mem://exp/ckpt")
    assert isinstance(backend.inner, MemoryStorage) and p == "mem://exp/ckpt"


def test_local_backend_roundtrip_and_listdir(tmp_path):
    backend = LocalStorage()
    path = str(tmp_path / "a" / "b.bin")
    backend.write_bytes(path, b"hello")
    assert backend.read_bytes(path) == b"hello"
    assert backend.exists(path)
    assert backend.listdir(str(tmp_path / "a")) == ["b.bin"]
    backend.delete(path)
    assert backend.read_bytes(path) is None


def _chunks():
    block = np.arange(50000, dtype=np.float32)
    return [b"head", memoryview(block.view(np.uint8)), b"", bytearray(b"tail")]


@pytest.mark.parametrize("scheme", ["local", "mem", "memory"])
def test_write_chunks_writes_what_write_bytes_would(tmp_path, scheme):
    """Local files, the in-process fake and an fsspec filesystem
    (``memory://``), each through ``get_storage``'s retry wrapper: the
    chunks' concatenation, as ``write_bytes`` of the joined payload."""
    pytest.importorskip("fsspec")
    root = {"local": str(tmp_path / "d"), "mem": "mem://chunks",
            "memory": "memory://chunks"}[scheme]
    backend, p = get_storage(root + "/a.bin")
    assert backend.write_chunks(p, _chunks()) == p
    whole = b"".join(_chunks())
    assert backend.read_bytes(p) == whole
    other, q = get_storage(root + "/b.bin")
    other.write_bytes(q, whole)
    assert other.read_bytes(q) == backend.read_bytes(p)
    assert backend.listdir(get_storage(root)[1]) == ["a.bin", "b.bin"]


class _Broken(Exception):
    pass


def _breaking_chunks():
    yield from _chunks()[:2]
    raise _Broken("the producer failed part-way")


def test_write_chunks_is_atomic_on_local(tmp_path):
    """A producer that fails part-way leaves the old file whole and no
    temporary file; so does a chunk the file cannot take."""
    backend = LocalStorage()
    path = str(tmp_path / "d" / "a.bin")
    backend.write_bytes(path, b"old")
    for chunks in (_breaking_chunks(), [b"new", "not bytes"]):
        with pytest.raises((_Broken, TypeError)):
            backend.write_chunks(path, chunks)
        assert backend.read_bytes(path) == b"old"
        assert backend.listdir(str(tmp_path / "d")) == ["a.bin"]


def test_write_chunks_failure_leaves_no_object_on_fsspec():
    pytest.importorskip("fsspec")
    backend, p = get_storage("memory://chunks_fail/a.bin")
    with pytest.raises(_Broken):
        backend.write_chunks(p, _breaking_chunks())
    assert not backend.exists(p)
    assert backend.read_bytes(p) is None


def test_write_chunks_default_joins_and_retries_from_the_start():
    """A backend that knows only ``write_bytes`` gets the joined payload;
    the retry wrapper iterates the chunks again for each attempt."""
    from distributed_machine_learning_tpu.tune.storage import (
        RetryingStorage,
        RetryPolicy,
        StorageBackend,
    )

    class WholePayloads(StorageBackend):
        def __init__(self):
            self.calls = []

        def write_bytes(self, path, data):
            self.calls.append((path, data))
            if len(self.calls) == 1:
                raise OSError("transient")
            return path

    class Chunks:
        passes = 0

        def __iter__(self):
            self.passes += 1
            return iter(_chunks())

    inner, chunks = WholePayloads(), Chunks()
    backend = RetryingStorage(inner, RetryPolicy(attempts=3, base_delay_s=0))
    assert backend.write_chunks("x/a.bin", chunks) == "x/a.bin"
    whole = b"".join(_chunks())
    assert inner.calls == [("x/a.bin", whole)] * 2
    assert chunks.passes == 2


def test_memory_backend_shared_namespace():
    a, b = MemoryStorage(), MemoryStorage()
    a.write_bytes("mem://exp/t0/ck1", b"x")
    assert b.read_bytes("mem://exp/t0/ck1") == b"x"  # one namespace
    assert b.listdir("mem://exp/t0") == ["ck1"]
    assert b.listdir("mem://exp") == ["t0"]


def test_checkpoint_roundtrip_mem():
    tree = {"params": {"w": np.arange(4.0).reshape(2, 2)}, "epoch": 3}
    path = "mem://ckpts/trial/ckpt_000003.msgpack"
    ckpt_lib.save_checkpoint(path, tree)
    raw = ckpt_lib.load_checkpoint(path)
    restored = ckpt_lib.restore_into(tree, raw)
    np.testing.assert_array_equal(restored["params"]["w"], tree["params"]["w"])
    assert int(restored["epoch"]) == 3


def test_load_missing_returns_none(tmp_path):
    assert ckpt_lib.load_checkpoint(str(tmp_path / "nope.msgpack")) is None
    assert ckpt_lib.load_checkpoint("mem://nope") is None
    assert ckpt_lib.load_checkpoint("") is None


@pytest.mark.parametrize("root", ["local", "mem"])
def test_prune_keeps_newest_and_protects(tmp_path, root):
    directory = (
        str(tmp_path / "cks") if root == "local" else "mem://exp/t/checkpoints"
    )
    paths = {}
    for it in range(1, 6):
        p = ckpt_lib.checkpoint_path(directory, it)
        ckpt_lib.save_checkpoint(p, {"epoch": it})
        paths[it] = p
    deleted = ckpt_lib.prune_checkpoints(directory, keep=2, protect=paths[1])
    assert deleted == 2  # 2 and 3 deleted; 1 protected; 4, 5 kept
    assert ckpt_lib.load_checkpoint(paths[1]) is not None
    assert ckpt_lib.load_checkpoint(paths[2]) is None
    assert ckpt_lib.load_checkpoint(paths[3]) is None
    assert ckpt_lib.load_checkpoint(paths[4]) is not None
    assert ckpt_lib.load_checkpoint(paths[5]) is not None


def test_experiment_store_checkpoint_root(tmp_path):
    store = ExperimentStore(str(tmp_path), "exp1",
                            checkpoint_storage="mem://bucket")
    t = Trial(trial_id="trial_00000", config={})
    assert store.checkpoint_dir(t) == "mem://bucket/exp1/trial_00000/checkpoints"
    # metrics stay on the local store
    assert store.root.startswith(str(tmp_path))


def _ckpt_trainable(config):
    """Reports a checkpoint each epoch; crashes once to force a restore."""
    import os

    restored = tune.get_checkpoint()
    start = int(restored["epoch"]) if restored else 0
    marker = os.path.join(config["marker_dir"], tune.get_trial_id())
    first = not os.path.exists(marker)
    if first:
        open(marker, "w").close()
    for epoch in range(start + 1, 7):
        if first and epoch == 4:
            raise RuntimeError("injected crash")
        tune.report(
            {"loss": 1.0 / epoch, "epoch": epoch},
            checkpoint={"epoch": epoch},
        )


def test_tune_run_checkpoints_to_memory_with_retention(tmp_path):
    """End-to-end: checkpoints land in the mem:// backend, retention keeps the
    last two, and the injected-crash retry restores from mem:// state."""
    marker_dir = tmp_path / "markers"
    marker_dir.mkdir()
    analysis = tune.run(
        _ckpt_trainable,
        {"marker_dir": str(marker_dir)},
        metric="loss",
        mode="min",
        num_samples=2,
        max_failures=1,
        storage_path=str(tmp_path),
        checkpoint_storage="mem://bucket",
        keep_checkpoints_num=2,
        verbose=0,
    )
    assert analysis.num_terminated() == 2
    for t in analysis.trials:
        # crashed at epoch 4, restored from the epoch-3 checkpoint, finished
        epochs = [r["epoch"] for r in t.results]
        assert epochs[-1] == 6 and 3 in epochs
        assert t.num_failures == 1
        assert t.latest_checkpoint.startswith("mem://bucket/")
        backend, d = get_storage(
            f"mem://bucket/{analysis.root.rsplit('/', 1)[-1]}/"
            f"{t.trial_id}/checkpoints"
        )
        names = [n for n in backend.listdir(d) if n.endswith(".msgpack")]
        assert len(names) <= 3  # keep 2 + possibly a protected restore target
        assert f"ckpt_{6:06d}.msgpack" in names

"""Gang trials: ONE trial owning a mesh that SPANS worker processes.

The ISSUE 14 acceptance surface, end to end through ``run_distributed(
processes_per_trial=2)`` against real worker supervisor subprocesses on
localhost:

* a 2-process gang trial is **bit-identical** (metric stream AND final
  params/opt-state bytes) to the same config through ``tune.run`` on a
  single process;
* the gang program key folds the process topology: the second
  same-topology gang (fresh workers, fresh compile cache, shared
  ``ArtifactRegistry``) fetches from the artifact origin and publishes
  nothing — it compiled nothing new;
* trace ids span the ``jax.distributed`` processes: head + both gang
  members write spans into ONE trace;
* chaos ``kill_process_at`` on one gang member mid-sweep → gang teardown,
  requeue from the newest valid checkpoint, and the faulted sweep finds
  the SAME best trial as the fault-free control;
* a gang member that never spawns trips the head's bootstrap deadline:
  flight dump NAMING the absent process ids, teardown, ERROR within the
  retry budget.

Every test is probe-gated on ``multiprocess_cpu_collectives`` — skipped
WITH the probe's evidence where this environment cannot run 2-process
jax.distributed CPU collectives at all.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

import _env_probe
from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.compilecache import (
    ArtifactRegistry,
    gang_program_key,
)
from distributed_machine_learning_tpu.data import Dataset
from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
from distributed_machine_learning_tpu.tune.cluster import (
    run_distributed,
    start_local_workers,
)
from distributed_machine_learning_tpu.tune.trial import TrialStatus

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _require_multiproc():
    ok, why = _env_probe.multiprocess_cpu_collectives()
    if not ok:
        pytest.skip(f"2-process jax.distributed unavailable here: {why}")


def _worker_env(**extra):
    keep = [
        p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([TESTS_DIR] + keep),
    }
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(96, 8, 4)).astype(np.float32)
    w = rng.normal(size=(4,)).astype(np.float32)
    y = (x.mean(axis=1) @ w)[:, None].astype(np.float32)
    return Dataset(x[:64], y[:64]), Dataset(x[64:], y[64:])


_CFG = {
    "model": "mlp", "hidden_sizes": (16, 8), "learning_rate": 0.01,
    "weight_decay": 1e-4, "seed": 3, "num_epochs": 3, "batch_size": 16,
    "loss_function": "mse", "optimizer": "adam", "lr_schedule": "constant",
}

_METRIC_KEYS = ("train_loss", "validation_loss", "validation_mae",
                "validation_mape")


def _trainable():
    train, val = _data()
    return tune.with_parameters(
        tune.train_sharded_regressor, train_data=train, val_data=val
    )


def _metric_stream(trial):
    return [{k: r[k] for k in _METRIC_KEYS} for r in trial.results]


def _run_gang_sweep(tmp_path, name, addrs, registry, **over):
    kw = dict(
        metric="validation_loss", mode="min", num_samples=1,
        workers=addrs, storage_path=str(tmp_path), name=name, verbose=0,
        checkpoint_format="sharded", processes_per_trial=2,
        mesh_shape={"dp": 2}, artifact_origin=registry,
        shutdown_workers=True,
    )
    kw.update(over)
    return run_distributed(_trainable(), dict(_CFG), **kw)


def _state(tmp_path, name):
    with open(os.path.join(
        str(tmp_path), name, "experiment_state.json"
    )) as f:
        return json.load(f)


def _leaves_bytes(tree):
    import jax

    leaves, _ = jax.tree_util.tree_flatten(tree)
    return [np.asarray(a).tobytes() for a in leaves]


@pytest.fixture
def worker_pair():
    """Two fresh single-slot supervisors (one gang of 2) with their own
    compile-cache dir; tears the subprocesses down hard."""
    pools = []

    def start(**extra):
        procs, addrs = start_local_workers(
            2, slots=1, env=_worker_env(**extra)
        )
        pools.append(procs)
        return addrs

    yield start
    for procs in pools:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()


def test_gang_trial_bit_identical_and_origin_dedup(
    tmp_path, worker_pair
):
    """The tentpole acceptance in one arc: (1) a trial spanning 2
    processes is bit-identical to the single-process run; (2) trace ids
    span the gang; (3) the second same-topology gang on FRESH workers
    fetches the first gang's artifacts and publishes nothing."""
    _require_multiproc()

    # Single-process reference: same config, same dp=2 mesh, one process.
    ref = tune.run(
        _trainable(), dict(_CFG), metric="validation_loss", mode="min",
        num_samples=1, mesh_shape={"dp": 2}, storage_path=str(tmp_path),
        name="ref", verbose=0, checkpoint_format="sharded",
    )
    assert ref.trials[0].status == TrialStatus.TERMINATED

    registry = ArtifactRegistry()
    addrs1 = worker_pair(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cacheA"))
    gang1 = _run_gang_sweep(tmp_path, "gang1", addrs1, registry,
                            trace=True)
    t = gang1.trials[0]
    assert t.status == TrialStatus.TERMINATED, t.error

    # (1) Bit-identical reported metric stream...
    assert _metric_stream(t) == _metric_stream(ref.trials[0])
    # ...and bit-identical final params + optimizer state, read back from
    # the generation the GANG saved from its process-spanning mesh (the
    # single-process restore side of the resharding format, for free).
    gen = f"gen_{_CFG['num_epochs']:06d}"
    ref_tree = ckpt_lib.load_checkpoint(os.path.join(
        str(tmp_path), "ref", "trial_00000", "checkpoints", gen))
    gang_tree = ckpt_lib.load_checkpoint(os.path.join(
        str(tmp_path), "gang1", "trial_00000", "checkpoints", gen))
    assert _leaves_bytes(gang_tree["params"]) == \
        _leaves_bytes(ref_tree["params"])
    assert _leaves_bytes(gang_tree["opt_state"]) == \
        _leaves_bytes(ref_tree["opt_state"])

    state1 = _state(tmp_path, "gang1")
    # All-zero liveness counters elide the block entirely.
    assert state1.get("liveness", {}).get("gang_teardowns", 0) == 0
    # First gang compiled and published its artifacts to the origin.
    assert state1["compile"]["origin_publishes"] >= 1

    # (2) One trace spans the jax.distributed processes: the head's file
    # plus BOTH gang members' files carry the same trace id.
    trace_files = glob.glob(os.path.join(
        str(tmp_path), "gang1", "trace", "trace_*.jsonl"))
    by_label = {}
    for path in trace_files:
        label = os.path.basename(path)[len("trace_"):].rsplit("_", 1)[0]
        with open(path) as f:
            for line in f:
                span = json.loads(line)
                by_label.setdefault(label, set()).add(span.get("trace_id"))
    gang_labels = [l for l in by_label if l.startswith("gang")]
    assert len(gang_labels) >= 2, by_label.keys()
    head_ids = by_label.get("head", set())
    assert head_ids
    for label in gang_labels:
        assert by_label[label] & head_ids, (
            f"{label} spans share no trace id with the head: "
            f"{by_label[label]} vs {head_ids}"
        )

    # (3) Second gang, SAME topology, FRESH workers and compile cache,
    # same origin registry: fetch hit, nothing published — it compiled
    # nothing the origin didn't already have.
    addrs2 = worker_pair(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cacheB"))
    gang2 = _run_gang_sweep(tmp_path, "gang2", addrs2, registry)
    assert gang2.trials[0].status == TrialStatus.TERMINATED
    assert _metric_stream(gang2.trials[0]) == _metric_stream(ref.trials[0])
    state2 = _state(tmp_path, "gang2")
    assert state2["compile"]["origin_fetch_hits"] >= 1
    assert state2["compile"]["origin_publishes"] == 0


def test_gang_validation_rejects_bad_configs():
    """Fail-fast surface: gang trials need sharded checkpoints, a mesh
    divisible across members, and at least N worker addresses."""
    with pytest.raises(ValueError, match="sharded"):
        run_distributed(
            _trainable(), dict(_CFG), metric="validation_loss",
            workers=["a:1", "b:1"], processes_per_trial=2,
        )
    with pytest.raises(ValueError, match="not divisible"):
        run_distributed(
            _trainable(), dict(_CFG), metric="validation_loss",
            workers=["a:1", "b:1"], processes_per_trial=2,
            checkpoint_format="sharded", mesh_shape={"dp": 3},
        )
    with pytest.raises(ValueError, match="at least"):
        run_distributed(
            _trainable(), dict(_CFG), metric="validation_loss",
            workers=["a:1"], processes_per_trial=2,
            checkpoint_format="sharded", mesh_shape={"dp": 2},
        )
    with pytest.raises(ValueError, match=">= 1"):
        run_distributed(
            _trainable(), dict(_CFG), metric="validation_loss",
            workers=["a:1"], processes_per_trial=0,
        )


def test_gang_program_key_splits_on_topology():
    """Reshaping the gang splits the key; the same topology does not."""
    cfg = dict(_CFG)
    k22 = gang_program_key(cfg, process_count=2, local_device_counts=[2, 2])
    k22_again = gang_program_key(
        cfg, process_count=2, local_device_counts=[2, 2]
    )
    k41 = gang_program_key(
        cfg, process_count=4, local_device_counts=[1, 1, 1, 1]
    )
    k14 = gang_program_key(cfg, process_count=1, local_device_counts=[4])
    assert k22 == k22_again
    assert len({k22, k41, k14}) == 3
    # lr/seed stay non-structural under the gang key too.
    assert k22 == gang_program_key(
        dict(cfg, learning_rate=0.5, seed=99),
        process_count=2, local_device_counts=[2, 2],
    )


def test_gang_member_kill_teardown_requeue_same_best(
    tmp_path, worker_pair
):
    """Chaos kill of one gang member mid-epoch: the head tears the gang
    down, requeues from the newest valid checkpoint, and the faulted
    sweep finds the SAME best trial — with the same final metrics — as
    the fault-free control."""
    _require_multiproc()

    space = dict(_CFG, learning_rate=tune.loguniform(5e-3, 5e-2))
    kw = dict(
        metric="validation_loss", mode="min", num_samples=2, seed=11,
        storage_path=str(tmp_path), verbose=0,
        checkpoint_format="sharded", processes_per_trial=2,
        mesh_shape={"dp": 2}, max_failures=2, shutdown_workers=True,
    )

    addrs = worker_pair(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cacheA"))
    control = run_distributed(
        _trainable(), space, workers=addrs, name="control", **kw
    )
    assert control.num_terminated() == 2

    # Kill gang process 1 (a NON-coordinator member) of the second trial
    # at its epoch-2 report boundary.  The plan reaches the gang child
    # through the supervisors' spawn env.
    plan = {"kill_process_at": [["trial_00001", 2, 1]]}
    addrs2 = worker_pair(
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cacheB"),
        DML_CHAOS_PLAN=json.dumps(plan),
    )
    faulted = run_distributed(
        _trainable(), space, workers=addrs2, name="faulted", **kw
    )
    assert faulted.num_terminated() == 2

    state = _state(tmp_path, "faulted")
    assert state["liveness"]["gang_teardowns"] >= 1
    assert state["liveness"]["gang_requeues"] >= 1

    # Deterministic recovery: same winner, same final metrics, same
    # sampled config — the requeued gang resumed from a committed
    # generation and replayed to the identical end state.
    assert faulted.best_trial.trial_id == control.best_trial.trial_id
    assert _metric_stream(faulted.best_trial) == \
        _metric_stream(control.best_trial)
    f1 = next(t for t in faulted.trials if t.trial_id == "trial_00001")
    c1 = next(t for t in control.trials if t.trial_id == "trial_00001")
    assert f1.results[-1]["validation_loss"] == \
        c1.results[-1]["validation_loss"]


def test_gang_bootstrap_timeout_dumps_absent_members(tmp_path):
    """A gang member that never spawns trips the head's all-joined
    deadline: flight dump naming the ABSENT process ids, teardown, and
    the trial errors within its (zero) retry budget."""
    _require_multiproc()

    # Worker 0 healthy; worker 1 holds its gang-member spawn far past the
    # join deadline (the straggler-host stand-in).
    procs0, addrs0 = start_local_workers(
        1, slots=1, env=_worker_env()
    )
    procs1, addrs1 = start_local_workers(
        1, slots=1, env=_worker_env(DML_GANG_SPAWN_HOLD_S="45"),
    )
    try:
        analysis = run_distributed(
            _trainable(), dict(_CFG),
            metric="validation_loss", mode="min", num_samples=1,
            workers=addrs0 + addrs1, storage_path=str(tmp_path),
            name="stuckgang", verbose=0, checkpoint_format="sharded",
            processes_per_trial=2, mesh_shape={"dp": 2},
            gang_join_deadline_s=5.0, max_failures=0,
            shutdown_workers=True,
        )
    finally:
        for p in procs0 + procs1:
            if p.poll() is None:
                p.terminate()
        for p in procs0 + procs1:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()

    t = analysis.trials[0]
    assert t.status == TrialStatus.ERROR
    assert "absent process ids" in (t.error or "")
    state = _state(tmp_path, "stuckgang")
    assert state["liveness"]["gang_bootstrap_timeouts"] >= 1
    assert state["liveness"]["gang_teardowns"] >= 1

    # The flight dump landed in the experiment root and NAMES the absent
    # members.  The held worker (process id 1) is necessarily among them;
    # member 0 may legitimately appear too — jax.distributed.initialize
    # blocks every member until ALL have connected, so a straggler keeps
    # its healthy peers from joining as well.
    dumps = glob.glob(os.path.join(
        str(tmp_path), "stuckgang", "flightrec_*gang_bootstrap_timeout*"))
    assert dumps, "no gang_bootstrap_timeout flight dump"
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert 1 in payload["extra"]["absent_process_ids"]

"""Multi-host control plane: driver <-> worker supervisors over TCP.

SURVEY.md §2b D4/D5: the capability the reference delegated to Ray Core —
cluster trial placement, metric RPC, fault handling — exercised here with
real worker subprocesses on localhost (the same supervisor binary a TPU pod
host would run).
"""

from __future__ import annotations

import os
import sys
import threading
import time

import pytest

from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.tune.cluster import (
    resolve_trainable,
    run_distributed,
    start_local_workers,
)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _worker_env():
    # Worker supervisors in these tests are CPU-only.
    keep = [
        p
        for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([TESTS_DIR] + keep),
    }


@pytest.fixture(scope="module")
def worker_pool():
    procs, addrs = start_local_workers(2, slots=2, env=_worker_env())
    yield addrs
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:
            p.kill()


def test_resolve_trainable_specs():
    fn = resolve_trainable("cluster_trainables:quadratic_trial")
    assert callable(fn)
    fn2 = resolve_trainable("os.path.join")
    assert fn2 is os.path.join
    assert resolve_trainable(fn) is fn


def test_distributed_sweep_completes(worker_pool, tmp_path):
    analysis = run_distributed(
        "cluster_trainables:quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 4},
        metric="loss",
        mode="min",
        num_samples=8,
        workers=worker_pool,
        storage_path=str(tmp_path),
        name="dist_smoke",
        seed=3,
        verbose=0,
    )
    assert analysis.num_terminated() == 8
    best = analysis.best_config
    assert 0.0 <= best["x"] <= 6.0
    # Best trial should be the sampled x closest to the optimum at 3.0.
    xs = [t.config["x"] for t in analysis.trials]
    assert abs(best["x"] - 3.0) == min(abs(x - 3.0) for x in xs)
    # Per-epoch streaming: every trial has one result per epoch.
    for t in analysis.trials:
        assert len(t.results) == 4
        assert t.results[-1]["hostname"]


def test_distributed_asha_early_stops(worker_pool, tmp_path):
    from distributed_machine_learning_tpu.tune.schedulers import ASHAScheduler

    analysis = run_distributed(
        "cluster_trainables:quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 8},
        metric="loss",
        mode="min",
        num_samples=8,
        workers=worker_pool,
        scheduler=ASHAScheduler(max_t=8, grace_period=1, reduction_factor=2),
        storage_path=str(tmp_path),
        name="dist_asha",
        seed=5,
        verbose=0,
    )
    assert analysis.num_terminated() == 8
    iters = [len(t.results) for t in analysis.trials]
    assert any(i < 8 for i in iters), f"ASHA never early-stopped: {iters}"
    assert any(i == 8 for i in iters)


def test_distributed_retry_restores_from_checkpoint(worker_pool, tmp_path):
    marker_dir = str(tmp_path / "markers")
    os.makedirs(marker_dir, exist_ok=True)
    analysis = run_distributed(
        "cluster_trainables:crash_once_trial",
        {"marker_dir": marker_dir},
        metric="loss",
        mode="min",
        num_samples=3,
        workers=worker_pool,
        max_failures=2,
        storage_path=str(tmp_path),
        name="dist_retry",
        verbose=0,
    )
    assert analysis.num_terminated() == 3
    for t in analysis.trials:
        assert t.num_failures == 1  # crashed once, then recovered
        epochs = [r["epoch"] for r in t.results]
        # epoch 1 reported pre-crash; retry restores from its checkpoint and
        # continues with 2, 3 rather than restarting at 1.
        assert epochs[0] == 1 and epochs[-1] == 3
        assert epochs.count(1) == 1


def test_distributed_pbt_exploits_and_restores(worker_pool, tmp_path):
    """PBT over the cluster: REQUEUE decisions stop a lagging trial, restore a
    donor checkpoint on a (possibly different) worker, and resume mid-stream —
    the full exploit/explore loop across the control plane."""
    from distributed_machine_learning_tpu.tune.schedulers import (
        PopulationBasedTraining,
    )

    barrier_dir = tmp_path / "barrier"
    barrier_dir.mkdir()
    pbt = PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"rate": tune.uniform(0.01, 0.5)},
        quantile_fraction=0.5,
        seed=11,
    )
    analysis = run_distributed(
        "cluster_trainables:pbt_trial",
        {"rate": tune.uniform(0.01, 0.5), "epochs": 8,
         "barrier_dir": str(barrier_dir), "population": 4},
        metric="loss",
        mode="min",
        num_samples=4,
        workers=worker_pool,
        scheduler=pbt,
        storage_path=str(tmp_path),
        name="dist_pbt",
        seed=9,
        verbose=0,
    )
    assert analysis.num_terminated() == 4
    # Every trial must reach the final epoch despite stop/respawn cycles.
    assert all(t.results[-1]["epoch"] == 8 for t in analysis.trials)
    # PBT must have acted: the barrier-paced population guarantees every
    # trial's scores are comparable when the interval fires, so the bottom
    # trial is requeued by construction.  (Epoch-sequence heuristics are NOT
    # a reliable respawn detector: a laggard stopped at epoch k and restored
    # from a donor checkpoint also at epoch k re-reports the plain staircase.)
    assert pbt.debug_state()["num_perturbations"] >= 1, "PBT never requeued"
    # The exploit actually routed donor weights: some trial restored from a
    # checkpoint it did not write itself.
    restored = [t for t in analysis.trials if t.restore_path]
    assert any(t.trial_id not in t.restore_path for t in restored)


def test_worker_death_requeues_trials(tmp_path):
    procs, addrs = start_local_workers(2, slots=2, env=_worker_env())
    result = {}

    def drive():
        # Capture, don't swallow: a raise in here must fail the test with
        # ITS traceback, not an opaque KeyError on the result dict.
        try:
            result["analysis"] = run_distributed(
                "cluster_trainables:slow_trial",
                {"epochs": 10, "sleep_s": 0.2},
                metric="loss",
                mode="min",
                num_samples=4,
                workers=addrs,
                max_failures=3,
                storage_path=str(tmp_path),
                name="dist_death",
                verbose=0,
            )
        except BaseException:
            import traceback

            result["error"] = traceback.format_exc()

    # All 4 trials land immediately (2 slots x 2 workers); killing one worker
    # mid-flight forces its 2 trials to requeue onto the survivor.
    t = threading.Thread(target=drive)
    t.start()
    time.sleep(1.0)
    procs[0].kill()
    t.join(timeout=120)
    assert not t.is_alive(), "driver hung after worker death"
    assert "error" not in result, f"run_distributed raised:\n{result['error']}"
    analysis = result["analysis"]
    done = analysis.num_terminated()
    assert done == 4, f"only {done}/4 trials finished after worker death"
    assert any(t_.num_failures > 0 for t_ in analysis.trials)
    for p in procs[1:]:
        p.terminate()


def test_trial_time_limit_over_cluster(worker_pool, tmp_path):
    """Per-trial time limits apply to cluster trials at report boundaries."""
    analysis = run_distributed(
        "cluster_trainables:slow_trial",
        {"epochs": 30, "sleep_s": 0.2},
        metric="loss",
        mode="min",
        num_samples=2,
        workers=worker_pool,
        time_limit_per_trial_s=1.0,
        storage_path=str(tmp_path),
        name="dist_tl",
        verbose=0,
    )
    for t in analysis.trials:
        assert t.status.value == "TERMINATED"
        assert 1 <= t.training_iteration < 30


def test_jax_runs_on_worker(worker_pool, tmp_path):
    analysis = run_distributed(
        "cluster_trainables:jax_device_trial",
        {"x": tune.choice([1.0, 2.0])},
        metric="loss",
        mode="min",
        num_samples=2,
        workers=worker_pool,
        storage_path=str(tmp_path),
        name="dist_jax",
        verbose=0,
    )
    assert analysis.num_terminated() == 2
    for t in analysis.trials:
        assert "cpu" in t.results[-1]["device"].lower()


def test_distributed_resume(worker_pool, tmp_path):
    """run_distributed(resume=True): interrupted trials redispatch from
    their checkpoints, finished ones stay finished, sampling continues."""
    import json

    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    first = run_distributed(
        "cluster_trainables:resumable_quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 4},
        metric="loss",
        mode="min",
        num_samples=3,
        workers=worker_pool,
        storage_path=str(tmp_path),
        name="dist_resume",
        seed=5,
        verbose=0,
    )
    assert first.num_terminated() == 3
    root = first.root
    # Simulate a driver crash with trial_00002 mid-flight at epoch 2.
    state_path = os.path.join(root, "experiment_state.json")
    with open(state_path) as f:
        state = json.load(f)
    for t in state["trials"]:
        if t["trial_id"] == "trial_00002":
            t["status"] = "RUNNING"
    with open(state_path, "w") as f:
        json.dump(state, f)
    results_path = os.path.join(root, "trial_00002", "result.jsonl")
    with open(results_path) as f:
        lines = [l for l in f if l.strip()]
    with open(results_path, "w") as f:
        f.writelines(lines[:2])

    resumed = run_distributed(
        "cluster_trainables:resumable_quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 4},
        metric="loss",
        mode="min",
        num_samples=4,
        workers=worker_pool,
        storage_path=str(tmp_path),
        name="dist_resume",
        seed=5,
        verbose=0,
        resume=True,
    )
    by_id = {t.trial_id: t for t in resumed.trials}
    assert len(by_id) == 4
    assert all(t.status == TrialStatus.TERMINATED for t in resumed.trials)
    assert by_id["trial_00002"].training_iteration == 4
    # A REAL checkpoint resume: only the 2 replayed pre-crash records remain
    # (the epoch-4 checkpoint survived, so the re-run had nothing to report).
    # A silent from-scratch re-run would show 4 records here.
    assert len(by_id["trial_00002"].results) == 2
    assert len(by_id["trial_00003"].results) == 4  # the newly sampled one


def test_hmac_authenticated_control_plane(tmp_path, monkeypatch):
    """With DML_CLUSTER_SECRET set on both sides, every frame is MACed and a
    sweep runs end-to-end; a driver with the WRONG secret is rejected at the
    hello (frames failing verification never reach pickle.loads)."""
    from distributed_machine_learning_tpu.tune.cluster import RemoteWorker

    secret_env = dict(_worker_env(), DML_CLUSTER_SECRET="s3cret")
    procs, addrs = start_local_workers(1, slots=2, env=secret_env)
    try:
        monkeypatch.setenv("DML_CLUSTER_SECRET", "s3cret")
        analysis = run_distributed(
            "cluster_trainables:quadratic_trial",
            {"x": tune.uniform(0.0, 6.0), "epochs": 2},
            metric="loss",
            mode="min",
            num_samples=2,
            workers=addrs,
            storage_path=str(tmp_path),
            name="dist_hmac",
            verbose=0,
        )
        assert analysis.num_terminated() == 2

        # Wrong secret: the worker's hello frame fails our MAC check.
        monkeypatch.setenv("DML_CLUSTER_SECRET", "wrong")
        with pytest.raises((ConnectionError, OSError)):
            RemoteWorker(addrs[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


def test_distributed_stop_rules(worker_pool, tmp_path):
    """stop= has the tune.run surface on the cluster driver too: trials cut
    at the threshold across the control plane."""
    analysis = run_distributed(
        "cluster_trainables:quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 6},
        metric="loss",
        mode="min",
        num_samples=3,
        workers=worker_pool,
        stop={"training_iteration": 2},
        storage_path=str(tmp_path),
        name="dist_stop",
        seed=11,
        verbose=0,
    )
    assert analysis.num_terminated() == 3
    assert all(len(t.results) == 2 for t in analysis.trials)


def test_distributed_callbacks_and_reporter(worker_pool, tmp_path, capsys):
    """run_distributed exposes the same observer surface as tune.run: every
    lifecycle hook fires on the driver thread, and verbose=2 attaches the
    live trial table."""

    class Recording(tune.Callback):
        def __init__(self):
            self.events = []

        def setup(self, root, metric, mode):
            self.events.append(("setup", metric, mode))

        def on_trial_start(self, trial):
            self.events.append(("start", trial.trial_id))

        def on_trial_result(self, trial, result):
            self.events.append(("result", trial.trial_id,
                                result.get("training_iteration")))

        def on_trial_complete(self, trial):
            self.events.append(("complete", trial.trial_id))

        def on_trial_error(self, trial, error):
            self.events.append(("error", trial.trial_id))

        def on_experiment_end(self, trials, wall):
            self.events.append(("end", len(trials)))

    cb = Recording()
    analysis = run_distributed(
        "cluster_trainables:quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 3},
        metric="loss", mode="min", num_samples=4,
        workers=worker_pool,
        storage_path=str(tmp_path), name="dist_cb", seed=5,
        verbose=2,
    )
    assert analysis.num_terminated() == 4
    out = capsys.readouterr().out
    assert "Final result" in out and "best loss:" in out  # verbose=2 table

    # The explicit-callback path sees the full lifecycle.
    cb2 = Recording()
    analysis = run_distributed(
        "cluster_trainables:quadratic_trial",
        {"x": tune.uniform(0.0, 6.0), "epochs": 2},
        metric="loss", mode="min", num_samples=3,
        workers=worker_pool,
        storage_path=str(tmp_path), name="dist_cb2", seed=6,
        verbose=0, callbacks=[cb2],
    )
    assert analysis.num_terminated() == 3
    kinds = [e[0] for e in cb2.events]
    assert kinds[0] == "setup" and cb2.events[0] == ("setup", "loss", "min")
    assert kinds[-1] == "end"
    assert kinds.count("start") == 3
    assert kinds.count("complete") == 3
    assert kinds.count("result") == 6  # 3 trials x 2 epochs


def test_distributed_mesh_shape_leases_device_groups(tmp_path):
    """run_distributed(mesh_shape=...) stamps the mesh into every config
    and each dispatch hands the trial prod(mesh_shape) DISTINCT local
    devices (worker slot groups) — the cluster side of the partition-rule
    sharding tentpole (ISSUE 7)."""
    procs, addrs = start_local_workers(1, slots=2, env=_worker_env())
    try:
        analysis = run_distributed(
            "cluster_trainables:mesh_probe_trial",
            {"x": tune.uniform(0.0, 1.0)},
            metric="loss", mode="min", num_samples=3,
            workers=addrs, storage_path=str(tmp_path), name="mesh_cluster",
            seed=2, verbose=0,
            mesh_shape={"dp": 2, "tp": 2},
        )
        assert analysis.num_terminated() == 3
        for t in analysis.trials:
            assert t.config["mesh_shape"] == {"dp": 2, "tp": 2}
            last = t.last_result
            assert last["n_devices"] == 4
            assert last["n_distinct"] == 4
            assert last["mesh_shape"] == {"dp": 2, "tp": 2}
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()

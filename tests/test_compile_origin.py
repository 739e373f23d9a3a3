"""Cluster compile-artifact origin: head-side registry, worker
fetch-before-compile / publish-after-compile, and the chaos fetch-fault
fallback (ISSUE 5 tentpole part 3 + chaos satellite).

Workers get DISTINCT persistent-cache directories (as distinct hosts
would), so a cross-worker cache hit can only come from the origin — the
thing under test."""

import json
import os

import pytest

from distributed_machine_learning_tpu import chaos, compilecache as cc, tune
from distributed_machine_learning_tpu.tune import cluster

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)


def _worker_env(cache_dir, extra=None):
    env = {
        "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
        "PYTHONPATH": os.pathsep.join([REPO_ROOT, TESTS_DIR]),
    }
    if extra:
        env.update(extra)
    return env


def _run_sweep(addrs, tmp_path, name, registry, num_samples=1, seed=3,
               space=None):
    return cluster.run_distributed(
        "cluster_trainables:compiling_trial",
        space or {"width": 12, "learning_rate": tune.uniform(0.5, 2.5),
                  "epochs": 2},
        metric="loss", workers=addrs, num_samples=num_samples, seed=seed,
        storage_path=str(tmp_path / "results"), name=name, verbose=0,
        shutdown_workers=True, artifact_origin=registry,
    )


def test_origin_second_worker_compiles_nothing(tmp_path):
    """Counter-verified cross-worker compile-once (acceptance 3b, the
    deterministic half): worker A compiles and publishes; worker B — a
    fresh process with an EMPTY cache dir — fetches the artifacts from the
    head and records ZERO uncached backend compiles for the same shape
    class."""
    registry = cc.ArtifactRegistry()
    results = []
    for i in range(2):
        procs, addrs = cluster.start_local_workers(
            1, slots=1, env=_worker_env(tmp_path / f"cache_w{i}"),
        )
        try:
            analysis = _run_sweep(
                addrs, tmp_path, f"origin_run{i}", registry, seed=3 + i,
            )
            results.append(analysis.trials[0].last_result)
        finally:
            for p in procs:
                p.terminate()
    first, second = results
    assert first["uncached_compiles"] > 0       # A really compiled
    assert first["worker_publishes"] == 1       # ... and published
    assert second["worker_fetch_hits"] == 1     # B fetched instead
    assert second["uncached_compiles"] == 0, second  # ... and compiled NOTHING
    snap = registry.snapshot()
    assert snap["origin_publishes"] == 1
    assert snap["origin_fetch_hits"] == 1


def test_origin_sweep_publishes_at_most_k_shape_classes(tmp_path):
    """N trials over K=2 shape classes on a 2-worker pool: the head-side
    registry records <= K publishes regardless of how trials raced —
    first-publish-wins makes "head-side compiles <= K" structural."""
    registry = cc.ArtifactRegistry()
    procs, addrs = [], []
    for i in range(2):
        p, a = cluster.start_local_workers(
            1, slots=1, env=_worker_env(tmp_path / f"kcache_w{i}"),
        )
        procs += p
        addrs += a
    try:
        analysis = _run_sweep(
            addrs, tmp_path, "origin_k", registry, num_samples=8,
            space={"width": tune.choice([8, 16]),
                   "learning_rate": tune.uniform(0.5, 2.5), "epochs": 2},
        )
    finally:
        for p in procs:
            p.terminate()
    assert analysis.num_terminated() == 8
    snap = registry.snapshot()
    assert 1 <= snap["origin_publishes"] <= 2, snap  # <= K = 2 shape classes
    assert snap["distinct_keys"] <= 2
    # Each worker compiles a shape class at most once: later same-class
    # trials ride the local caches (no fetch round trip, no compile).
    per_class_compiles = {}
    for t in analysis.trials:
        w = t.config["width"]
        per_class_compiles.setdefault(w, []).append(
            t.last_result["uncached_compiles"]
        )
    for width, compiles in per_class_compiles.items():
        assert sum(1 for c in compiles if c > 0) <= 2, (width, compiles)


def test_faulted_artifact_fetch_falls_back_to_local_compile(tmp_path):
    """Chaos satellite: with artifact_fetch_error_rate=1.0 on the workers,
    every fetch dies BEFORE reaching the head — workers must fall back to
    compiling locally (counted), the sweep must complete, and it must find
    the SAME best trial as the fault-free control (test_chaos.py pattern)."""
    space = {"width": tune.choice([8, 16]),
             "learning_rate": tune.uniform(0.5, 2.5), "epochs": 2}

    def sweep(name, chaos_env):
        registry = cc.ArtifactRegistry()
        procs, addrs = [], []
        for i in range(2):
            p, a = cluster.start_local_workers(
                1, slots=1,
                env=_worker_env(tmp_path / f"{name}_cache_w{i}", chaos_env),
            )
            procs += p
            addrs += a
        try:
            analysis = _run_sweep(
                addrs, tmp_path, name, registry, num_samples=6, seed=11,
                space=space,
            )
        finally:
            for p in procs:
                p.terminate()
        return analysis, registry

    control, _ = sweep("fetch_control", None)
    plan_json = json.dumps({"seed": 7, "artifact_fetch_error_rate": 1.0})
    faulted, reg = sweep(
        "fetch_faulted", {chaos.PLAN_ENV_VAR: plan_json}
    )

    assert faulted.num_terminated() == 6
    # Faults really fired and the fallback really ran: no fetch ever
    # reached the head, and every trial still produced results (local
    # compiles on both workers).
    assert reg.snapshot()["origin_fetch_hits"] == 0
    assert reg.snapshot()["origin_fetch_misses"] == 0
    fallbacks = [
        t.last_result.get("worker_fetch_fallbacks", 0)
        for t in faulted.trials
    ]
    assert max(fallbacks) >= 1, fallbacks
    # Recovery is invisible to the search: same best trial as the control.
    assert faulted.best_trial.trial_id == control.best_trial.trial_id
    assert faulted.best_result["loss"] == pytest.approx(
        control.best_result["loss"], rel=1e-6
    )


def test_origin_second_worker_compiles_nothing_sharded(tmp_path):
    """ISSUE 7 acceptance: compile-once holds for SHARDED programs.
    Worker A compiles the mesh-sharded program and publishes; worker B —
    fresh process, empty cache dir, SAME mesh shape — fetches and records
    ZERO uncached backend compiles.  Worker C on a DIFFERENT mesh shape
    over the same devices must NOT reuse it: the program key folds in the
    mesh shape, so C honestly recompiles."""
    registry = cc.ArtifactRegistry()

    def sweep(i, mesh_shape, seed):
        procs, addrs = cluster.start_local_workers(
            1, slots=1, env=_worker_env(tmp_path / f"shcache_w{i}"),
        )
        try:
            analysis = cluster.run_distributed(
                "cluster_trainables:sharded_compiling_trial",
                {"width": 16, "learning_rate": tune.uniform(0.5, 2.5),
                 "epochs": 2},
                metric="loss", workers=addrs, num_samples=1, seed=seed,
                mesh_shape=mesh_shape,
                storage_path=str(tmp_path / "results"),
                name=f"sh_origin_run{i}", verbose=0,
                shutdown_workers=True, artifact_origin=registry,
            )
            return analysis.trials[0].last_result
        finally:
            for p in procs:
                p.terminate()

    first = sweep(0, {"dp": 2, "tp": 2}, seed=3)
    second = sweep(1, {"dp": 2, "tp": 2}, seed=4)
    third = sweep(2, {"dp": 4, "tp": 1}, seed=5)

    assert first["n_devices"] == 4
    assert first["uncached_compiles"] > 0        # A really compiled
    assert first["worker_publishes"] >= 1        # ... and published
    assert second["worker_fetch_hits"] >= 1      # B fetched instead
    assert second["uncached_compiles"] == 0, second  # ... compiled NOTHING
    # Same (config, rules) on a reshaped mesh is a DIFFERENT program:
    # the sharded key splits, and the worker honestly recompiles.
    assert third["sharded_key"] != first["sharded_key"]
    assert third["uncached_compiles"] > 0, third
    assert first["sharded_key"] == second["sharded_key"]

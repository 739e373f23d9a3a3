"""Fail-slow fault tolerance: watchdogs, lease expiry, fencing, requeue.

PR 2's chaos tests prove recovery from faults that ANNOUNCE themselves;
everything here is about silence — a dispatch that sleeps instead of
raising, a worker that hangs while keeping its TCP connection open, a
partition that delays frames without dropping the socket.  Faults are
injected through the same seeded ``chaos.FaultPlan`` choke points
(``hang_dispatch_at``, ``partition_worker``), reaching worker
subprocesses via ``DML_CHAOS_PLAN``, and every test asserts both that the
injection fired (plan counters) and that the liveness counters in
``experiment_state.json`` tell the story.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from distributed_machine_learning_tpu import chaos, tune
from distributed_machine_learning_tpu.liveness import (
    DispatchWatchdog,
    Heartbeat,
)
from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
from distributed_machine_learning_tpu.tune.cluster import (
    run_distributed,
    start_local_workers,
)
from distributed_machine_learning_tpu.tune.trial import TrialStatus

pytestmark = pytest.mark.chaos

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _always_deactivate():
    yield
    chaos.deactivate()


# --------------------------------------------------------------------------
# liveness primitives
# --------------------------------------------------------------------------


def test_heartbeat_is_monotonic_and_counts():
    hb = Heartbeat()
    assert hb.beats == 0
    a0 = hb.age_s()
    time.sleep(0.02)
    assert hb.age_s() > a0
    hb.beat()
    assert hb.beats == 1
    assert hb.age_s() < 0.02


def test_watchdog_fires_once_per_episode_and_counts_recovery():
    dog = DispatchWatchdog(0.05, first_beat_grace_s=0.0)
    dog.track("t")
    time.sleep(0.08)
    events = dog.expired()
    assert [e.key for e in events] == ["t"]
    assert events[0].age_s > events[0].deadline_s
    # Edge-triggered: the same stall episode never fires twice.
    assert dog.expired() == []
    assert dog.is_stalled("t")
    # A beat on a stalled key is a recovery and re-arms detection.
    dog.beat("t")
    assert not dog.is_stalled("t")
    time.sleep(0.08)
    assert [e.key for e in dog.expired()] == ["t"]
    snap = dog.snapshot()
    assert snap["stalls_detected"] == 2
    assert snap["stall_recoveries"] == 1
    # Late beats for untracked keys are ignored, not resurrected.
    dog.untrack("t")
    dog.beat("t")
    assert dog.expired() == []


def test_watchdog_first_beat_grace_covers_startup():
    dog = DispatchWatchdog(0.03, first_beat_grace_s=10.0)
    dog.track("starting")
    time.sleep(0.06)
    assert dog.expired() == []  # still inside the cold-start grace
    dog.beat("starting")  # first beat: steady-state deadline from here on
    time.sleep(0.06)
    assert [e.key for e in dog.expired()] == ["starting"]


def test_watchdog_monitor_thread_invokes_on_stall():
    seen = []
    dog = DispatchWatchdog(
        0.04, on_stall=lambda e: seen.append(e.key), poll_s=0.01,
        first_beat_grace_s=0.0,
    ).start()
    try:
        with dog.guard("blocked", info={"what": "dispatch"}):
            time.sleep(0.12)  # the "blocking dispatch"
        assert seen == ["blocked"]
        # guard untracked on exit: no further events for it.
        time.sleep(0.06)
        assert seen == ["blocked"]
    finally:
        dog.close()


def test_newest_valid_checkpoint_skips_damaged_generations(tmp_path):
    from distributed_machine_learning_tpu.tune.storage import get_storage

    d = str(tmp_path)
    for i in (1, 2, 3):
        ckpt_lib.save_checkpoint(
            ckpt_lib.checkpoint_path(d, i), {"gen": float(i)}
        )
    backend, _ = get_storage(d)
    p3 = ckpt_lib.checkpoint_path(d, 3)
    backend.write_bytes(p3, chaos.corrupt_bytes(backend.read_bytes(p3)))
    path, it = ckpt_lib.newest_valid_checkpoint(d)
    assert it == 2 and path == ckpt_lib.checkpoint_path(d, 2)
    # All generations damaged -> (None, 0), the from-scratch signal.
    for i in (1, 2):
        p = ckpt_lib.checkpoint_path(d, i)
        backend.write_bytes(p, chaos.corrupt_bytes(backend.read_bytes(p)))
    assert ckpt_lib.newest_valid_checkpoint(d) == (None, 0)


# --------------------------------------------------------------------------
# tune.run: watchdog fires and recovers (thread) / kills and restarts
# (process)
# --------------------------------------------------------------------------


def _ckpt_trainable(config):
    restored = tune.get_checkpoint()
    start = int(restored["epoch"]) + 1 if restored else 0
    for epoch in range(start, int(config.get("epochs", 5))):
        tune.report(
            {"loss": 1.0 / (epoch + 1), "epoch": epoch},
            checkpoint={"epoch": epoch},
        )


def test_thread_executor_marks_stall_and_recovery(tmp_path):
    """Thread executor cannot preempt: an injected hang must be flagged
    STALLED, then clear as a recovery when the report resumes — and the
    trial still finishes normally."""
    plan = chaos.FaultPlan(
        seed=1, hang_dispatch_at=[("trial_00000", 3)], hang_s=1.0
    )
    with chaos.active(plan):
        analysis = tune.run(
            _ckpt_trainable,
            {"x": tune.uniform(0, 1), "epochs": 5},
            metric="loss", num_samples=2,
            storage_path=str(tmp_path), name="stall_thread", verbose=0,
            progress_deadline_s=0.25,
        )
    assert plan.snapshot()["dispatch_hangs"] == 1
    assert analysis.num_terminated() == 2
    t0 = {t.trial_id: t for t in analysis.trials}["trial_00000"]
    assert t0.status == TrialStatus.TERMINATED
    assert t0.stall_count >= 1
    assert t0.stall_recoveries >= 1
    assert [r["epoch"] for r in t0.results] == [0, 1, 2, 3, 4]
    state = json.load(open(f"{analysis.root}/experiment_state.json"))
    lv = state["liveness"]
    assert lv["stalls_detected"] >= 1
    assert lv["stall_recoveries"] >= 1
    assert lv["stall_kills"] == 0  # threads are marked, never killed
    assert state["injected_faults"]["dispatch_hangs"] == 1


def test_process_executor_kills_stalled_incarnation_and_restores(tmp_path):
    """The preemption-capable path: a hang past the deadline gets the
    incarnation SIGTERMed and the retry restores the newest checkpoint —
    no epoch is lost, one failure is charged to the retry budget."""
    plan = chaos.FaultPlan(
        seed=1, hang_dispatch_at=[("trial_00000", 3)], hang_s=3.0
    )
    with chaos.active(plan):
        analysis = tune.run(
            _ckpt_trainable,
            {"x": tune.uniform(0, 1), "epochs": 5},
            metric="loss", num_samples=1, max_failures=1,
            storage_path=str(tmp_path), name="stall_proc", verbose=0,
            trial_executor="process",
            progress_deadline_s=0.5, progress_grace_s=60.0,
        )
    t0 = analysis.trials[0]
    assert t0.status == TrialStatus.TERMINATED
    assert t0.num_failures == 1
    # Restored from the epoch-2 checkpoint: every epoch reported exactly
    # once across the two incarnations.
    assert [r["epoch"] for r in t0.results] == [0, 1, 2, 3, 4]
    state = json.load(open(f"{analysis.root}/experiment_state.json"))
    lv = state["liveness"]
    assert lv["stall_kills"] >= 1
    assert lv["stall_requeues"] >= 1


def test_vectorized_dispatch_watchdog_flags_hang(tmp_path, capfd):
    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=64, seq_len=6, num_features=4
    )
    plan = chaos.FaultPlan(
        seed=3, hang_dispatch_at=[("vectorized", 2)], hang_s=0.8
    )
    with chaos.active(plan):
        analysis = tune.run_vectorized(
            {"model": "mlp", "hidden_sizes": (8,),
             "learning_rate": tune.loguniform(1e-3, 1e-1),
             "num_epochs": 3, "batch_size": 32, "lr_schedule": "constant"},
            train_data=train, val_data=val, metric="validation_loss",
            num_samples=4, storage_path=str(tmp_path), name="stall_vec",
            verbose=0, epochs_per_dispatch=1,
            progress_deadline_s=0.25, progress_grace_s=60.0,
        )
    assert analysis.num_terminated() == 4
    state = json.load(open(f"{analysis.root}/experiment_state.json"))
    assert state["liveness"]["stalls_detected"] >= 1
    assert state["injected_faults"]["dispatch_hangs"] == 1
    # Stall forensics reach stderr immediately (the bench parent's
    # post-kill diagnosis channel).
    assert "dispatch stalled" in capfd.readouterr().err


# --------------------------------------------------------------------------
# cluster: hung-worker stall fencing + the partition acceptance e2e
# --------------------------------------------------------------------------


def test_startup_scaled_grace_math():
    """First-beat grace = max(configured/default fixed grace, SCALE x the
    worker's measured spawn time): load-proportional, never below the
    old behavior, and steady-state deadlines untouched."""
    from distributed_machine_learning_tpu.tune.cluster import (
        STARTUP_GRACE_SCALE,
        startup_scaled_grace,
    )

    # idle host: the fixed grace (explicit or default) is the floor
    assert startup_scaled_grace(1.2, 30.0, 0.0) == 30.0
    assert startup_scaled_grace(1.2, None, 0.0) == 30.0  # max(3*d, 30)
    assert startup_scaled_grace(20.0, None, 0.0) == 60.0
    # loaded host: measured spawn dominates
    assert startup_scaled_grace(1.2, 30.0, 60.0) == (
        STARTUP_GRACE_SCALE * 60.0
    )
    # the scaled term can only RAISE the grace, never lower it
    assert startup_scaled_grace(1.2, 45.0, 1.0) == 45.0
    assert startup_scaled_grace(1.2, 0.5, -3.0) == 0.5  # junk clamps


def test_slow_worker_startup_does_not_stall_trials(tmp_path):
    """Loaded-host regression for the worker-startup deadline flake (PR 9
    and PR 11 full runs): a host whose worker spawn is stretched (here:
    deterministically, via DML_CLUSTER_STARTUP_SLEEP_S standing in for a
    loaded host's jax import) runs trials whose first report takes longer
    than the FIXED first-beat threshold (deadline 0.4s + grace 0.5s <
    ~1s first epoch) — with the grace scaled from the worker's measured
    spawn time, none of them is spuriously stalled or requeued."""
    from distributed_machine_learning_tpu.liveness import DispatchWatchdog

    # The fixed threshold really is too small for this workload: a
    # watchdog with the UNscaled grace flags the key before the first
    # beat lands (the old behavior this test regresses against).
    dog = DispatchWatchdog(0.4, first_beat_grace_s=0.5)
    dog.track("would-stall")
    time.sleep(1.0)
    assert [e.key for e in dog.expired()] == ["would-stall"]

    procs, addrs = start_local_workers(
        1, slots=2,
        env=_worker_env({"DML_CLUSTER_STARTUP_SLEEP_S": "2.5"}),
    )
    try:
        analysis = run_distributed(
            "cluster_trainables:slow_resumable_trial",
            # ONE ~1s epoch per trial: everything between dispatch and the
            # first report is cold start (the window the scaled grace
            # covers); no steady-state gap ever exceeds the 0.4s deadline
            # because the first report is also the last.
            {"x": tune.uniform(0.0, 6.0), "epochs": 1, "sleep_s": 1.0},
            metric="loss", mode="min", num_samples=2,
            workers=addrs, storage_path=str(tmp_path),
            name="lv_slow_spawn", seed=3, verbose=0,
            progress_deadline_s=0.4, progress_grace_s=0.5,
        )
        assert analysis.num_terminated() == 2
        state = json.load(open(f"{analysis.root}/experiment_state.json"))
        lv = state.get("liveness", {})
        assert lv.get("stalls_detected", 0) == 0, (
            f"slow startup read as a stall despite scaled grace: {lv}"
        )
        assert lv.get("stall_requeues", 0) == 0
        assert all(t.num_failures == 0 for t in analysis.trials)
    finally:
        _terminate(procs)


def _worker_env(extra=None):
    keep = [
        p
        for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([TESTS_DIR] + keep),
        "DML_CLUSTER_HEARTBEAT_S": "0.2",
    }
    if extra:
        env.update(extra)
    return env


def _terminate(procs):
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:  # noqa: BLE001
            p.kill()


def test_cluster_stalled_trial_is_fenced_and_requeued(tmp_path):
    """A worker whose TRIAL hangs keeps heartbeating (supervisor healthy),
    so only the per-trial progress watchdog can catch it: the trial is
    fenced on the hung worker and requeued from its checkpoint."""
    plan_env = json.dumps(
        {"seed": 5, "hang_dispatch_at": [["trial_00000", 3]], "hang_s": 4.0}
    )
    procs, addrs = start_local_workers(
        2, slots=2, env=_worker_env({"DML_CHAOS_PLAN": plan_env})
    )
    try:
        analysis = run_distributed(
            "cluster_trainables:slow_resumable_trial",
            {"x": tune.uniform(0.0, 6.0), "epochs": 5, "sleep_s": 0.15},
            metric="loss", mode="min", num_samples=4,
            workers=addrs, max_failures=2,
            storage_path=str(tmp_path), name="lv_stall", seed=7, verbose=0,
            worker_heartbeat_timeout_s=5.0,
            progress_deadline_s=1.0, progress_grace_s=30.0,
        )
        assert analysis.num_terminated() == 4
        t0 = {t.trial_id: t for t in analysis.trials}["trial_00000"]
        assert t0.num_failures == 1
        # Requeued from the epoch-2 checkpoint: the epoch stream stays
        # exactly once-per-epoch across incarnations.
        assert [r["epoch"] for r in t0.results] == [1, 2, 3, 4, 5]
        state = json.load(open(f"{analysis.root}/experiment_state.json"))
        lv = state["liveness"]
        assert lv["stalls_detected"] >= 1
        assert lv["stall_requeues"] >= 1
        assert lv["lease_expiries"] == 0  # the worker never went silent
    finally:
        _terminate(procs)


def test_partition_requeue_replays_from_last_reported_generation(tmp_path):
    """Regression for the at-least-once fencing race (ISSUE 7, documented
    in docs/operations.md): a partitioned worker's checkpoint write
    reaches shared storage while its report frame sits buffered, so at
    requeue time the newest VALID generation is one the driver never saw
    reported.  Pre-fix, ``requeue_lost`` restored it and the retry
    resumed PAST the lost report — that epoch vanished from the stream
    forever (the 1-in-8 flake in the partition e2e).  Post-fix the
    unreported generation is quarantined (renamed) and the retry replays
    from the last *reported* generation, so every trial's epoch stream
    stays exactly once-per-epoch across incarnations."""
    procs, addrs = start_local_workers(2, slots=2, env=_worker_env())
    # Driver-side partition: at the 3rd result frame, worker 1's frames
    # (both directions) buffer for 2.5s.  Its running trials each save
    # their next checkpoint straight to tmp_path storage, send the report
    # into the buffer, and block on the decision — the exact
    # checkpoint-durable / report-lost state the race needs.
    plan = chaos.FaultPlan(seed=11, partition_worker=[(3, 1, 2.5)])
    try:
        with chaos.active(plan):
            analysis = run_distributed(
                "cluster_trainables:slow_resumable_trial",
                {"x": tune.uniform(0.0, 6.0), "epochs": 5, "sleep_s": 0.2},
                metric="loss", mode="min", num_samples=4,
                workers=addrs, max_failures=2,
                storage_path=str(tmp_path), name="lv_quarantine", seed=3,
                verbose=0,
                worker_heartbeat_timeout_s=0.8,
                worker_reconnect_grace_s=15.0,
            )
        assert plan.snapshot()["worker_partitions"] == 1
        assert analysis.num_terminated() == 4
        requeued = [t for t in analysis.trials if t.num_failures > 0]
        assert requeued, "the partition should have requeued something"
        for t in analysis.trials:
            # THE regression assertion: no epoch ever skipped (pre-fix:
            # the unreported epoch was missing) and none double-reported.
            assert [r["epoch"] for r in t.results] == [1, 2, 3, 4, 5], (
                t.trial_id
            )
        state = json.load(open(f"{analysis.root}/experiment_state.json"))
        lv = state["liveness"]
        assert lv["lease_expiries"] >= 1
        assert lv["quarantined_checkpoints"] >= 1
    finally:
        _terminate(procs)


def test_wallclock_jump_does_not_expire_live_worker_lease(
    tmp_path, monkeypatch
):
    """Regression for the dmlint ``wallclock-deadline`` fix sites (ISSUE 6
    satellite): lease expiry / last_seen / reconnect-grace arithmetic in
    tune/cluster.py must ride time.monotonic().  The driver's view of the
    wall clock flip-flops between now and now-2h — every consecutive pair
    of reads sees a +/-7200 s NTP-style step, so the old time.time() lease
    math would observe a worker 'silent' for two hours within the first
    few frames and expire it.  The monotonic clock is proxied through
    untouched; a healthy worker's lease must survive the whole sweep."""
    import time as real_time

    from distributed_machine_learning_tpu.tune import cluster as cluster_mod

    class JumpyTime:
        """time-module proxy scoped to cluster.py: wall jumps, the rest
        (monotonic, sleep, strftime) passes through."""

        def __init__(self):
            self.calls = 0

        def time(self):
            self.calls += 1
            return real_time.time() - (7200.0 if self.calls % 2 else 0.0)

        def __getattr__(self, name):
            return getattr(real_time, name)

    jumpy = JumpyTime()
    monkeypatch.setattr(cluster_mod, "time", jumpy)

    procs, addrs = start_local_workers(1, slots=2, env=_worker_env())
    try:
        analysis = run_distributed(
            "cluster_trainables:resumable_quadratic_trial",
            {"x": tune.uniform(0.0, 6.0), "epochs": 3},
            metric="loss", mode="min", num_samples=3,
            workers=addrs, storage_path=str(tmp_path), name="lv_ntp",
            seed=11, verbose=0,
            worker_heartbeat_timeout_s=60.0,
            worker_reconnect_grace_s=30.0,
        )
        assert analysis.num_terminated() == 3
        state = json.load(open(f"{analysis.root}/experiment_state.json"))
        lv = state.get("liveness", {})
        assert lv.get("lease_expiries", 0) == 0, (
            f"a wall-clock step expired a live worker's lease: {lv}"
        )
        assert lv.get("worker_requeues", 0) == 0
        # The proxy really was consulted (the sweep records wall_clock_s
        # through it), so a silent revert to raw time.time() cannot pass
        # by never exercising the jump.
        assert jumpy.calls > 0
    finally:
        _terminate(procs)


def test_cluster_partition_e2e_same_best_as_fault_free(tmp_path):
    """The acceptance e2e (ISSUE 3): one worker hangs a dispatch AND one
    worker is partition-injected mid-sweep — the faulted sweep requeues
    the affected trials from checkpoint within their retry budget, the
    healed worker self-fences its zombies, and the sweep reports the SAME
    best trial as the fault-free control run."""
    control_procs, control_addrs = start_local_workers(
        2, slots=2, env=_worker_env()
    )

    def sweep(addrs, name, **kwargs):
        return run_distributed(
            "cluster_trainables:slow_resumable_trial",
            {"x": tune.uniform(0.0, 6.0), "epochs": 8, "sleep_s": 0.2},
            metric="loss", mode="min", num_samples=6,
            workers=addrs, max_failures=2,
            storage_path=str(tmp_path), name=name, seed=7, verbose=0,
            **kwargs,
        )

    try:
        control = sweep(control_addrs, "lv_control")
        assert control.num_terminated() == 6
    finally:
        _terminate(control_procs)

    # Faulted run: worker-side hang (via env) + driver-side partition.
    plan_env = json.dumps(
        {"seed": 5, "hang_dispatch_at": [["trial_00004", 2]], "hang_s": 4.0}
    )
    procs, addrs = start_local_workers(
        2, slots=2, env=_worker_env({"DML_CHAOS_PLAN": plan_env})
    )
    plan = chaos.FaultPlan(seed=5, partition_worker=[(4, 1, 2.0)])
    try:
        with chaos.active(plan):
            faulted = sweep(
                addrs, "lv_faulted",
                worker_heartbeat_timeout_s=0.8,
                worker_reconnect_grace_s=15.0,
                progress_deadline_s=1.2, progress_grace_s=30.0,
            )
        snap = plan.snapshot()
        assert snap["worker_partitions"] == 1

        assert faulted.num_terminated() == 6  # every trial recovered
        assert any(t.num_failures > 0 for t in faulted.trials)

        # Same winner, same config, same loss: the trainable is
        # deterministic in x and every requeue restored a checkpoint.
        assert faulted.best_trial.trial_id == control.best_trial.trial_id
        assert faulted.best_config == control.best_config
        assert faulted.best_result["loss"] == pytest.approx(
            control.best_result["loss"], rel=1e-9
        )

        # The artifact carries the whole failure story.
        state = json.load(open(f"{faulted.root}/experiment_state.json"))
        lv = state["liveness"]
        assert lv["lease_expiries"] >= 1        # partition went silent
        assert lv["silent_worker_requeues"] >= 1
        assert lv["worker_reconnects"] >= 1     # ...and healed in grace
        assert lv["stalls_detected"] >= 1       # the hung dispatch
        assert lv["fenced_frames"] >= 1         # zombies were fenced
        assert state["injected_faults"]["worker_partitions"] == 1
        # Retry budget respected: nobody burned more than max_failures.
        assert all(t.num_failures <= 2 for t in faulted.trials)
    finally:
        _terminate(procs)


# --------------------------------------------------------------------------
# serve: a hung replica trips the breaker through the request deadline
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def liveness_bundle(tmp_path_factory):
    from distributed_machine_learning_tpu import serve
    from distributed_machine_learning_tpu.data import dummy_regression_data

    tmp = tmp_path_factory.mktemp("liveness_serve")
    train, val = dummy_regression_data(
        num_samples=96, seq_len=6, num_features=4, seed=3
    )
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {"model": "mlp", "hidden_sizes": (16,), "learning_rate": 0.01,
         "num_epochs": 2, "batch_size": 32, "lr_schedule": "constant"},
        metric="validation_loss", num_samples=1,
        storage_path=str(tmp), name="src", verbose=0,
    )
    out = str(tmp / "bundle")
    serve.export_bundle(analysis, out)
    return serve.load_bundle(out), val


def test_hung_replica_times_out_and_trips_breaker(liveness_bundle):
    import numpy as np

    from distributed_machine_learning_tpu import serve

    bundle, val = liveness_bundle
    rs = serve.ReplicaSet(
        bundle, num_replicas=1, max_bucket=8,
        breaker_failure_threshold=1, breaker_recovery_s=30.0,
    )
    try:
        x = np.asarray(val.x[:2], np.float32)
        rs.predict(x, timeout=5.0)  # healthy warm call

        # Wedge the replica: its engine blocks far past any deadline, so
        # the future never resolves — the exact failure the breaker's
        # outcome callback alone can never see.
        real_predict = rs.replicas[0].engine.predict
        rs.replicas[0].engine.predict = (
            lambda a: time.sleep(30.0) or real_predict(a)
        )
        with pytest.raises(serve.ReplicaTimeout) as ei:
            rs.predict(x, timeout=0.3)
        assert ei.value.replica_idx == 0
        assert rs.timeouts == 1
        # The deadline miss counted as a breaker failure (threshold 1):
        # the slot is quarantined, so the next request is load-shed
        # instead of burning another timeout on the wedged replica.
        assert rs._breakers[0].state == "open"
        with pytest.raises(serve.AllReplicasOpen):
            rs.predict(x, timeout=0.3)
    finally:
        rs.close()


def test_server_maps_timeout_to_504_and_counts_it(liveness_bundle):
    import urllib.error
    import urllib.request

    import numpy as np

    from distributed_machine_learning_tpu import serve

    bundle, val = liveness_bundle
    srv = serve.PredictionServer(
        bundle, port=0, num_replicas=1, max_bucket=8,
        request_timeout_s=0.3,
        breaker_failure_threshold=1, breaker_recovery_s=0.2,
    )
    try:
        host, port = srv.start()
        base = f"http://{host}:{port}"
        x = np.asarray(val.x[:2], np.float32).tolist()
        body = json.dumps({"instances": x}).encode()

        def post():
            req = urllib.request.Request(
                f"{base}/predict", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.loads(resp.read())

        post()  # healthy
        real_predict = srv.replicas.replicas[0].engine.predict
        srv.replicas.replicas[0].engine.predict = (
            lambda a: time.sleep(30.0) or real_predict(a)
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            post()
        assert ei.value.code == 504
        payload = json.loads(ei.value.read())
        assert payload["timeout_s"] == pytest.approx(0.3)
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            m = json.loads(resp.read())
        assert m["timeouts_total"] == 1
        assert m["breakers"]["request_failures_total"] >= 1
    finally:
        srv.close()

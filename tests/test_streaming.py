"""Out-of-core streaming (ISSUE 10): the double-buffered prefetch ring.

The contract under test, end to end:

* **budget**: a dataset larger than the device budget trains to completion
  streaming, while resident staging provably FAILS the budget check
  (``ResidentOverBudgetError`` from both ``Dataset.as_jax`` and an
  explicit ``input_mode="resident"`` trial);
* **determinism**: streaming and resident runs of the same seed see
  identical batches in identical order and finish with BIT-identical
  params (and identical validation streams / best trial) through
  ``tune.run``;
* **failure surfaces**: a chaos-crashed producer follows the ordinary
  trial error path (retry from checkpoint within ``max_failures``), a
  chaos-slowed producer degrades overlap efficiency but never
  correctness, and producer silence is a counted liveness stall;
* **observability**: the ``host_input`` counter block (chunks staged,
  prefetch hits, waits, overlap efficiency) lands in
  ``experiment_state.json``.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

import jax

from distributed_machine_learning_tpu import chaos, tune
from distributed_machine_learning_tpu.compilecache import chunked_program_key
from distributed_machine_learning_tpu.data import dummy_regression_data
from distributed_machine_learning_tpu.data import pipeline as hostpipe
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.tune import session
from distributed_machine_learning_tpu.tune.checkpoint import (
    find_latest_checkpoint,
    load_checkpoint,
)
from distributed_machine_learning_tpu.tune.trial import TrialStatus

BUDGET_ENV = "DML_CPU_DEVICE_BUDGET_BYTES"


@pytest.fixture(scope="module")
def small_data():
    return dummy_regression_data(num_samples=200, seq_len=8, num_features=6)


@pytest.fixture(scope="module")
def big_data():
    # ~520 KB staged (x: 2000*8*8*4) — "big" against the tiny virtual
    # budgets the tests below set, instant to build.
    return dummy_regression_data(num_samples=2000, seq_len=8, num_features=8)


def _standalone_run(trainable, config, train, val, devices=None):
    records = []

    sess = session.Session(
        trial=session._StandaloneTrial(),
        report_fn=lambda m, c: records.append((m, c)) or "continue",
        checkpoint_loader=lambda: None,
        devices=devices,
    )
    session.set_session(sess)
    try:
        trainable(config, train_data=train, val_data=val)
    finally:
        session.set_session(None)
    return records


# ---------------------------------------------------------------------------
# engagement policy / budget check
# ---------------------------------------------------------------------------


def test_resolve_input_mode_policy(monkeypatch):
    monkeypatch.setenv(BUDGET_ENV, str(1 << 20))  # 1 MiB
    # auto: under the engage fraction -> resident; over -> streaming.
    assert hostpipe.resolve_input_mode({}, 100_000) == "resident"
    assert hostpipe.resolve_input_mode({}, 600_000) == "streaming"
    # the fraction is a config knob
    assert hostpipe.resolve_input_mode(
        {"streaming_engage_fraction": 0.05}, 100_000
    ) == "streaming"
    # explicit streaming always streams, even tiny
    assert hostpipe.resolve_input_mode(
        {"input_mode": "streaming"}, 10
    ) == "streaming"
    # explicit resident under budget is honored, over budget raises
    assert hostpipe.resolve_input_mode(
        {"input_mode": "resident"}, 900_000
    ) == "resident"
    with pytest.raises(hostpipe.ResidentOverBudgetError):
        hostpipe.resolve_input_mode({"input_mode": "resident"}, 2 << 20)
    # sharded: per-device share is what counts
    assert hostpipe.resolve_input_mode(
        {"input_mode": "resident"}, 2 << 20, shards=4
    ) == "resident"
    with pytest.raises(ValueError):
        hostpipe.resolve_input_mode({"input_mode": "nope"}, 10)


def test_as_jax_enforce_budget(monkeypatch, big_data):
    train, _ = big_data
    monkeypatch.setenv(BUDGET_ENV, str(64 << 10))
    with pytest.raises(hostpipe.ResidentOverBudgetError):
        train.as_jax(enforce_budget=True)
    # small dataset passes the same check
    small = Dataset(train.x[:4].copy(), train.y[:4].copy())
    x, y = small.as_jax(enforce_budget=True)
    assert int(x.shape[0]) == 4 and int(y.shape[0]) == 4


# ---------------------------------------------------------------------------
# chunk planning + program keys
# ---------------------------------------------------------------------------


def test_plan_chunks_geometry(monkeypatch):
    monkeypatch.setenv(BUDGET_ENV, str(1 << 20))
    plan = hostpipe.plan_chunks(50, 32, row_nbytes=1024)
    assert plan.num_chunks * plan.chunk_batches + plan.tail_batches == 50
    assert plan.chunks_per_epoch == plan.num_chunks + (
        1 if plan.tail_batches else 0
    )
    starts = list(plan.chunk_sizes())
    assert starts[0] == (0, plan.chunk_batches)
    assert sum(rows for _, rows in starts) == 50
    # explicit override wins and clamps to the epoch
    plan2 = hostpipe.plan_chunks(
        10, 32, row_nbytes=1024, config={"streaming_chunk_batches": 64}
    )
    assert plan2.chunk_batches == 10 and plan2.tail_batches == 0
    # a huge per-batch footprint still yields a valid (1-batch) chunk
    plan3 = hostpipe.plan_chunks(7, 32, row_nbytes=10 << 20)
    assert plan3.chunk_batches == 1 and plan3.num_chunks == 7


def test_chunked_program_key_folds_rows_not_count():
    cfg = {"model": "mlp", "learning_rate": 1e-3, "batch_size": 32}
    shape = [[4, 32, 8, 6], [4, 32, 1]]
    k1 = chunked_program_key(cfg, chunk_rows=4, batch_shape=shape,
                             dtype="float32", donation=(0, 1, 2, 4, 5))
    # Same slab geometry, different dataset length / chunk count: the key
    # MUST NOT move (the host loops over chunks; the trace never sees the
    # count).  There is no count argument to pass — that absence is the
    # contract; identical inputs give identical keys across processes.
    k2 = chunked_program_key(cfg, chunk_rows=4, batch_shape=shape,
                             dtype="float32", donation=(0, 1, 2, 4, 5))
    assert k1 == k2
    # Rows (slab geometry) DO split the key.
    k3 = chunked_program_key(cfg, chunk_rows=8,
                             batch_shape=[[8, 32, 8, 6], [8, 32, 1]],
                             dtype="float32", donation=(0, 1, 2, 4, 5))
    assert k3 != k1
    # Non-structural hyperparameters do not.
    k4 = chunked_program_key(dict(cfg, learning_rate=0.5, seed=7),
                             chunk_rows=4, batch_shape=shape,
                             dtype="float32", donation=(0, 1, 2, 4, 5))
    assert k4 == k1


# ---------------------------------------------------------------------------
# the prefetch ring (unit)
# ---------------------------------------------------------------------------


def test_prefetch_ring_hits_waits_and_done():
    counters = hostpipe.HostInputCounters()

    def source():
        for i in range(6):
            yield np.full((4,), i, np.float32)

    ring = hostpipe.ChunkPrefetcher(
        source(), depth=2, deadline_s=5.0, counters=counters
    )
    got = []
    try:
        while True:
            try:
                got.append(ring.get())
            except StopIteration:
                break
    finally:
        ring.close()
    assert [int(a[0]) for a in got] == list(range(6))
    snap = counters.snapshot()
    assert snap["chunks_staged"] == 6
    assert snap["bytes_staged"] == 6 * 16
    # 6 chunk gets + the terminal (StopIteration) get — each is either a
    # hit or a wait.  Trainables pull exactly chunks_per_epoch items, so
    # the sentinel never skews their per-epoch accounting.
    assert snap["prefetch_hits"] + snap["consumer_waits"] == 7


def test_prefetch_ring_propagates_producer_crash():
    counters = hostpipe.HostInputCounters()

    def source():
        yield np.zeros(2, np.float32)
        raise RuntimeError("producer exploded")

    ring = hostpipe.ChunkPrefetcher(
        source(), depth=2, deadline_s=5.0, counters=counters
    )
    try:
        ring.get()
        with pytest.raises(RuntimeError, match="producer exploded"):
            # Crash may land while the ring still owes us a chunk.
            ring.get()
            ring.get()
    finally:
        ring.close()
    assert counters.snapshot()["producer_crashes"] == 1


def test_prefetch_ring_counts_producer_stall_and_hard_timeout():
    counters = hostpipe.HostInputCounters()
    release = threading.Event()

    def source():
        yield np.zeros(2, np.float32)
        release.wait(10.0)  # silent producer: no beat, nothing staged
        yield np.ones(2, np.float32)

    ring = hostpipe.ChunkPrefetcher(
        source(), depth=2, deadline_s=0.1, hard_timeout_s=0.6,
        counters=counters,
    )
    try:
        ring.get()
        with pytest.raises(hostpipe.ProducerStalled):
            ring.get()
    finally:
        release.set()
        ring.close()
    snap = counters.snapshot()
    assert snap["producer_stalls"] >= 1  # the liveness watchdog fired
    assert snap["consumer_waits"] >= 1 and snap["consumer_wait_s"] > 0


def test_overlap_efficiency_derivation():
    assert hostpipe.overlap_efficiency({}) is None
    assert hostpipe.overlap_efficiency(
        {"consume_s": 9.0, "consumer_wait_s": 1.0}
    ) == pytest.approx(0.9)
    assert hostpipe.overlap_efficiency(
        {"consume_s": 0.0, "consumer_wait_s": 2.0}
    ) == 0.0


# ---------------------------------------------------------------------------
# the headline: over-budget dataset trains streaming; resident fails
# ---------------------------------------------------------------------------


def test_over_budget_dataset_trains_streaming_resident_fails(
    monkeypatch, big_data, tmp_results
):
    train, val = big_data
    monkeypatch.setenv(BUDGET_ENV, str(64 << 10))  # 64 KiB virtual budget
    assert hostpipe.staged_nbytes(train, val, np.float32) > (64 << 10)

    config = {
        "model": "mlp", "hidden_sizes": (16,), "learning_rate": 1e-3,
        "batch_size": 64, "num_epochs": 2, "lr_schedule": "constant",
    }
    # Resident staging provably fails the budget check...
    with pytest.raises(hostpipe.ResidentOverBudgetError):
        _standalone_run(
            tune.train_regressor, dict(config, input_mode="resident"),
            train, val,
        )
    # ...while auto engages streaming and trains to completion through
    # tune.run — validation streamed too (it exceeds the engage fraction).
    base = hostpipe.get_host_input_counters().snapshot()
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        config,
        metric="validation_loss",
        num_samples=1,
        storage_path=tmp_results,
        name="stream_over_budget",
        verbose=0,
    )
    trial = analysis.trials[0]
    assert trial.status == TrialStatus.TERMINATED
    assert trial.training_iteration == 2
    assert trial.last_result["input_mode"] == "streaming"
    delta = hostpipe.get_host_input_counters().delta_since(base)
    assert delta["streams_engaged"] == 1
    assert delta["chunks_staged"] > 0 and delta["bytes_staged"] > 0
    # The host_input block is a property of the artifact.
    state = json.load(open(os.path.join(analysis.root,
                                        "experiment_state.json")))
    hi = state["host_input"]
    assert hi["chunks_staged"] > 0
    assert 0.0 <= hi["overlap_efficiency"] <= 1.0


# ---------------------------------------------------------------------------
# determinism contract through tune.run
# ---------------------------------------------------------------------------


def _run_mode(mode, data, tmp_results, name):
    train, val = data
    return tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {
            "model": "mlp", "hidden_sizes": (32, 16),
            "learning_rate": tune.loguniform(1e-3, 1e-1),
            "batch_size": 32, "num_epochs": 3, "lr_schedule": "constant",
            # Several chunks per epoch so boundaries are actually crossed.
            "streaming_chunk_batches": 2,
        },
        metric="validation_loss",
        num_samples=2,
        seed=11,
        input_mode=mode,
        storage_path=tmp_results,
        name=name,
        verbose=0,
    )


def test_streaming_resident_bit_parity_e2e(small_data, tmp_results):
    """Same seed, both modes: identical sampled configs, identical
    validation streams, the SAME best trial, and bit-identical final
    params from the stored checkpoints."""
    res = _run_mode("resident", small_data, tmp_results, "parity_resident")
    stm = _run_mode("streaming", small_data, tmp_results, "parity_streaming")
    assert [t.config["learning_rate"] for t in res.trials] == \
        [t.config["learning_rate"] for t in stm.trials]
    assert res.best_trial.trial_id == stm.best_trial.trial_id
    for tr, ts in zip(res.trials, stm.trials):
        hr = tr.metric_history("validation_loss")
        hs = ts.metric_history("validation_loss")
        assert hr == hs  # bit-identical eval stream, every epoch
        cr = load_checkpoint(find_latest_checkpoint(
            os.path.join(res.root, tr.trial_id, "checkpoints"))[0])
        cs = load_checkpoint(find_latest_checkpoint(
            os.path.join(stm.root, ts.trial_id, "checkpoints"))[0])
        leaves_r = jax.tree.leaves(cr["params"])
        leaves_s = jax.tree.leaves(cs["params"])
        assert len(leaves_r) == len(leaves_s) > 0
        for a, b in zip(leaves_r, leaves_s):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_trial_emits_the_resident_trials_spans(small_data):
    """Both loops share their set-up, so a streaming trial's spans carry
    the resident trial's names under the same parents (PERF.md section 3
    reads them by name)."""
    from distributed_machine_learning_tpu import obs

    train, val = small_data
    shared = {"trial.setup", "trial.build", "trial.init_or_restore",
              "epoch", "epoch.dispatch", "epoch.readback"}

    def edges(mode):
        tracer = obs.Tracer()
        obs.install_tracer(tracer)
        try:
            _standalone_run(
                tune.train_regressor,
                {"model": "mlp", "hidden_sizes": (8,), "learning_rate": 1e-3,
                 "num_epochs": 2, "batch_size": 32, "input_mode": mode},
                train, val,
            )
        finally:
            obs.install_tracer(None)
        records = tracer.records()
        names = {  # add_complete records (compile events) have no id
            r["args"]["span_id"]: r["name"]
            for r in records if "span_id" in r["args"]
        }
        return sorted(
            (r["name"], names.get(r["args"].get("parent_id")))
            for r in records if r["name"] in shared
        )

    resident = edges("resident")
    assert ("trial.build", "trial.setup") in resident
    assert ("trial.init_or_restore", "trial.setup") in resident
    assert resident.count(("epoch.dispatch", "epoch")) == 2
    assert resident.count(("epoch.readback", "epoch")) == 2
    assert edges("streaming") == resident


# ---------------------------------------------------------------------------
# failure surfaces: producer crash, slow producer
# ---------------------------------------------------------------------------


def test_producer_crash_retries_cleanly(small_data, tmp_results):
    train, val = small_data
    plan = chaos.FaultPlan(seed=3, producer_crash_at=4)
    with chaos.active(plan):
        analysis = tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            {
                "model": "mlp", "hidden_sizes": (16,),
                "learning_rate": 1e-2, "batch_size": 32, "num_epochs": 4,
                "lr_schedule": "constant", "input_mode": "streaming",
                "streaming_chunk_batches": 2,
            },
            metric="validation_loss",
            num_samples=1,
            max_failures=1,
            storage_path=tmp_results,
            name="stream_producer_crash",
            verbose=0,
        )
    trial = analysis.trials[0]
    assert trial.status == TrialStatus.TERMINATED
    assert trial.training_iteration == 4  # finished despite the crash
    assert trial.num_failures == 1
    assert plan.snapshot()["producer_crashes"] == 1
    state = json.load(open(os.path.join(analysis.root,
                                        "experiment_state.json")))
    assert state["injected_faults"]["producer_crashes"] == 1


def test_slow_producer_degrades_overlap_not_params(small_data, tmp_results):
    """Chaos slow-producer: waits pile up (overlap efficiency drops) but
    the params are bit-identical to an unfaulted streaming run — the
    counters absorb the slowdown, never the numerics."""
    train, val = small_data
    config = {
        "model": "mlp", "hidden_sizes": (16,), "learning_rate": 1e-2,
        "batch_size": 32, "num_epochs": 2, "lr_schedule": "constant",
        "input_mode": "streaming", "streaming_chunk_batches": 1,
    }
    clean = _standalone_run(tune.train_regressor,
                            dict(config, checkpoint_freq=2), train, val)
    base = hostpipe.get_host_input_counters().snapshot()
    plan = chaos.FaultPlan(seed=5, slow_producer_ms=20)
    with chaos.active(plan):
        slowed = _standalone_run(tune.train_regressor,
                                 dict(config, checkpoint_freq=2), train, val)
    assert plan.snapshot()["producer_slowdowns"] > 0
    delta = hostpipe.get_host_input_counters().delta_since(base)
    assert delta["consumer_waits"] > 0  # the device had to wait
    for a, b in zip(jax.tree.leaves(clean[-1][1]["params"]),
                    jax.tree.leaves(slowed[-1][1]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# drivers: vectorized fallback
# ---------------------------------------------------------------------------


def test_vectorized_streaming_falls_back_counted(small_data, tmp_results):
    train, val = small_data
    base = hostpipe.get_host_input_counters().snapshot()
    analysis = tune.run_vectorized(
        {
            "model": "mlp", "hidden_sizes": (16,),
            "learning_rate": tune.loguniform(1e-3, 1e-2),
            "batch_size": 32, "num_epochs": 2, "lr_schedule": "constant",
        },
        train_data=train,
        val_data=val,
        metric="validation_loss",
        num_samples=2,
        input_mode="streaming",
        storage_path=tmp_results,
        name="vec_stream_fallback",
        verbose=0,
    )
    assert analysis.num_terminated() == 2
    delta = hostpipe.get_host_input_counters().delta_since(base)
    assert delta["mode_fallbacks"] == 1
    state = json.load(open(os.path.join(analysis.root,
                                        "experiment_state.json")))
    hi = state["host_input"]
    assert hi["mode_fallbacks"] == 1
    assert hi["input_mode_requested"] == "streaming"
    with pytest.raises(ValueError):
        tune.run_vectorized(
            {"model": "mlp", "learning_rate": 1e-3},
            train_data=train, val_data=val, metric="validation_loss",
            input_mode="bogus", storage_path=tmp_results, verbose=0,
        )


# ---------------------------------------------------------------------------
# sharded streaming on the 2x4 probe-gated mesh
# ---------------------------------------------------------------------------

from tests import _env_probe  # noqa: E402 - gating import, test-file idiom

_PROBE_OK, _PROBE_WHY = _env_probe.sharded_2d_mesh()
needs_sharded_mesh = pytest.mark.skipif(
    not _PROBE_OK, reason=f"environment evidence: {_PROBE_WHY}"
)


@needs_sharded_mesh
def test_sharded_streaming_matches_resident_on_2x4_mesh():
    train, val = dummy_regression_data(
        num_samples=256, seq_len=8, num_features=6, seed=3
    )
    config = {
        "model": "mlp", "hidden_sizes": (16,), "learning_rate": 1e-3,
        "batch_size": 32, "num_epochs": 2, "seed": 5, "checkpoint_freq": 2,
        "mesh_shape": {"dp": 2, "tp": 4}, "lr_schedule": "constant",
    }
    devices = jax.devices()[:8]
    base = hostpipe.get_host_input_counters().snapshot()
    res = _standalone_run(
        tune.train_sharded_regressor, dict(config, input_mode="resident"),
        train, val, devices=devices,
    )
    stm = _standalone_run(
        tune.train_sharded_regressor,
        dict(config, input_mode="streaming", streaming_chunk_batches=3),
        train, val, devices=devices,
    )
    delta = hostpipe.get_host_input_counters().delta_since(base)
    assert delta["streams_engaged"] == 1 and delta["chunks_staged"] > 0
    assert stm[-1][0]["input_mode"] == "streaming"
    for (mr, _), (ms, _) in zip(res, stm):
        assert mr["validation_loss"] == ms["validation_loss"]
    for a, b in zip(jax.tree.leaves(res[-1][1]["params"]),
                    jax.tree.leaves(stm[-1][1]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

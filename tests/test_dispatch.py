"""Device-dispatch serialization (utils/dispatch.py): these tests pin
the resolution rules, the mutual exclusion, and that a thread-executor
run still trains correctly when serialized."""

import threading
import time

from distributed_machine_learning_tpu.utils import dispatch


def _resolve_with(monkeypatch, flag=None):
    monkeypatch.setattr(dispatch, "_resolved", None)
    if flag is None:
        monkeypatch.delenv("DML_SERIALIZE_DISPATCH", raising=False)
    else:
        monkeypatch.setenv("DML_SERIALIZE_DISPATCH", flag)
    return dispatch._serialize_on()


def test_default_off(monkeypatch):
    # Unset means off, whatever else the environment holds.
    assert _resolve_with(monkeypatch) is False


def test_env_forces_on_and_off(monkeypatch):
    assert _resolve_with(monkeypatch, flag="1") is True
    assert _resolve_with(monkeypatch, flag="0") is False


def test_lock_is_noop_when_off(monkeypatch):
    _resolve_with(monkeypatch)
    ctx = dispatch.dispatch_lock()
    assert not isinstance(ctx, type(dispatch._LOCK))
    with ctx:
        pass


def test_lock_serializes_threads_and_is_reentrant(monkeypatch):
    _resolve_with(monkeypatch, flag="1")
    in_section = []
    overlaps = []

    def work(i):
        with dispatch.dispatch_lock():
            with dispatch.dispatch_lock():  # reentrant
                in_section.append(i)
                if len(in_section) > 1:
                    overlaps.append(tuple(in_section))
                time.sleep(0.02)
                in_section.remove(i)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not overlaps


def test_thread_executor_run_trains_under_serialization(monkeypatch):
    """A real concurrent tune.run with serialization forced on: trials
    still complete and report finite losses (the lock must not deadlock
    against the cohort build lock or the scheduler)."""
    monkeypatch.setattr(dispatch, "_resolved", None)
    monkeypatch.setenv("DML_SERIALIZE_DISPATCH", "1")
    try:
        from distributed_machine_learning_tpu import tune
        from distributed_machine_learning_tpu.data import (
            dummy_regression_data,
        )

        train, val = dummy_regression_data(
            num_samples=64, seq_len=8, num_features=4
        )
        analysis = tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            {"model": "mlp", "hidden_dims": [8],
             "learning_rate": tune.loguniform(1e-3, 1e-2),
             "num_epochs": 2, "batch_size": 16,
             "seed": tune.randint(0, 10_000)},
            metric="validation_loss", mode="min", num_samples=3,
            verbose=0,
        )
        assert len(analysis.trials) == 3
        best = analysis.best_result["validation_loss"]
        assert best == best  # finite, not NaN
    finally:
        monkeypatch.setattr(dispatch, "_resolved", None)

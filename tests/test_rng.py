"""Dropout PRNG impl selection (ops/rng.py): auto-resolution and the
population checkpoint's record of which impl produced its key data."""

import jax
import numpy as np
import pytest

from distributed_machine_learning_tpu.ops.rng import resolve_rng_impl


def test_resolver_explicit_values_win(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_rng_impl({"rng_impl": "threefry"}) is None
    assert resolve_rng_impl({"rng_impl": "rbg"}) == "rbg"


def test_resolver_auto_by_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_rng_impl({}) == "rbg"
    assert resolve_rng_impl(None) == "rbg"
    assert resolve_rng_impl({"rng_impl": "auto"}) == "rbg"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_rng_impl({}) is None


def test_resolved_impl_wraps_key_data():
    """The resolver's outputs are valid jax.random.key impls, and key data
    round-trips through wrap_key_data under the same impl (the population
    checkpoint/restore contract in tune/vectorized.py)."""
    for impl in (resolve_rng_impl({"rng_impl": "rbg"}),
                 resolve_rng_impl({"rng_impl": "threefry"})):
        key = jax.random.key(7, impl=impl)
        data = np.asarray(jax.random.key_data(key))
        rewrapped = jax.random.wrap_key_data(data, impl=impl)
        a = jax.random.uniform(key, (3,))
        b = jax.random.uniform(rewrapped, (3,))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rbg_and_threefry_key_data_shapes_differ():
    """Why the checkpoint must record the impl: the raw key data of the two
    impls is not interchangeable."""
    rbg = np.asarray(jax.random.key_data(jax.random.key(0, impl="rbg")))
    tf = np.asarray(jax.random.key_data(jax.random.key(0)))
    assert rbg.shape != tf.shape
    with pytest.raises(Exception):
        jax.random.wrap_key_data(
            np.asarray(tf), impl="rbg"
        )  # wrong-width data must not silently wrap


def _checkpoints_of(config, train, val, checkpoint=None):
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import session

    reports = []
    session.set_session(session.Session(
        session._StandaloneTrial(),
        lambda rec, ck=None: reports.append((rec, ck)),
        lambda: checkpoint,
    ))
    try:
        tune.train_regressor(config, train_data=train, val_data=val)
    finally:
        session.set_session(None)
    return [c for _, c in reports if c is not None]


def _stored(tmp_path, checkpoint, drop=()):
    from distributed_machine_learning_tpu.tune.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(
        path, {k: v for k, v in checkpoint.items() if k not in drop}
    )
    return load_checkpoint(path)


@pytest.fixture(scope="module")
def rng_trial():
    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=64, seq_len=6, num_features=3
    )
    config = {"model": "mlp", "learning_rate": 1e-3, "num_epochs": 1,
              "batch_size": 32, "dropout": 0.1, "rng_impl": "rbg",
              "seed": 3}
    return config, train, val


@pytest.mark.parametrize("input_mode", ["resident", "streaming"])
def test_trainable_checkpoint_records_and_restores_rng_impl(
    tmp_path, rng_trial, input_mode
):
    """A trial's checkpoint records the resolved dropout-PRNG impl, and a
    restore reuses the RECORDED impl even when the restoring config/backend
    would resolve differently (cross-backend resume must not mix stream
    families mid-trial)."""
    config, train, val = rng_trial
    config = dict(config, input_mode=input_mode)
    ckpts = _checkpoints_of(config, train, val)
    assert ckpts and ckpts[-1]["rng_impl"] == "rbg"

    # Restore under a config whose own resolution differs (rng_impl absent:
    # auto -> threefry on CPU). The recorded impl must win; the new
    # checkpoint re-records the inherited impl, and training completes
    # (rbg-wide epoch keys keep working).
    cfg2 = dict(config, num_epochs=2)
    del cfg2["rng_impl"]
    ckpts2 = _checkpoints_of(
        cfg2, train, val, checkpoint=_stored(tmp_path, ckpts[-1])
    )
    assert ckpts2 and ckpts2[-1]["rng_impl"] == "rbg"


@pytest.mark.parametrize("input_mode", ["resident", "streaming"])
def test_checkpoint_without_rng_impl_continues_under_the_raw_config_value(
    tmp_path, rng_trial, input_mode, monkeypatch
):
    """A checkpoint from before the impl was recorded: its epochs were
    drawn under the RAW config value (there was no auto-resolution then),
    so the restore continues with that and not with what this backend
    would resolve to."""
    from distributed_machine_learning_tpu.tune import trainable

    config, train, val = rng_trial
    config = dict(config, input_mode=input_mode)
    old = _stored(
        tmp_path, _checkpoints_of(config, train, val)[-1], drop=("rng_impl",)
    )
    # As on a TPU: whatever the config says resolves to the hardware RNG.
    monkeypatch.setattr(trainable, "resolve_rng_impl", lambda config: "rbg")
    unset = dict(config, num_epochs=2)
    del unset["rng_impl"]
    assert _checkpoints_of(unset, train, val)[-1]["rng_impl"] == "rbg"
    resumed = _checkpoints_of(unset, train, val, checkpoint=old)
    assert [c["epoch"] for c in resumed] == [1]
    assert resumed[-1]["rng_impl"] == ""  # the raw value: jax's default
    kept = _checkpoints_of(
        dict(config, num_epochs=2), train, val, checkpoint=old
    )
    assert kept[-1]["rng_impl"] == "rbg"

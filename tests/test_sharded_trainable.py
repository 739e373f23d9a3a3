"""Multi-device trials through ``tune.run`` on the virtual 8-device CPU mesh.

Closes VERDICT r1 #3: the flagship multi-chip path (``resources_per_trial=
{"devices": N}`` -> DeviceManager lease -> mesh -> GSPMD-sharded train step)
runs under the tune API and matches single-device numerics.  Reference hook:
``resources_per_trial`` (`/root/reference/ray-tune-hpo-regression.py:475`).
"""

import jax
import numpy as np
import pytest

from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import dummy_regression_data
from distributed_machine_learning_tpu.tune.trial import TrialStatus


@pytest.fixture(scope="module")
def data():
    return dummy_regression_data(num_samples=256, seq_len=8, num_features=4)


BASE_CONFIG = {
    "model": "mlp",
    "hidden_sizes": (16,),
    "dropout": 0.0,
    "learning_rate": 0.01,
    "weight_decay": 0.0,
    "num_epochs": 4,
    "batch_size": 32,
    "lr_schedule": "constant",
    "seed": 3,
}


def _run(data, config, num_samples=1, **kwargs):
    train, val = data
    return tune.run(
        tune.with_parameters(
            tune.train_sharded_regressor, train_data=train, val_data=val
        ),
        config,
        metric="validation_loss",
        num_samples=num_samples,
        storage_path=kwargs.pop("storage_path"),
        verbose=0,
        **kwargs,
    )


def test_four_device_dp_trial_e2e(data, tmp_path):
    """BASELINE config 5 shape: one trial spanning 4 leased devices."""
    analysis = _run(
        data, dict(BASE_CONFIG), storage_path=str(tmp_path),
        resources_per_trial={"devices": 4},
    )
    t = analysis.trials[0]
    assert t.status == TrialStatus.TERMINATED
    assert t.training_iteration == 4
    assert t.last_result["num_devices"] == 4
    losses = t.metric_history("validation_loss")
    assert losses[-1] < losses[0]  # it learns


def test_dp_matches_single_device_losses(data, tmp_path):
    """Numeric parity: the 4-device dp trajectory equals the 1-device one.

    Same seed => same init, same shuffle order, same global batches; GSPMD
    splits each batch over dp and all-reduces grads, which is the same math
    up to float re-association."""
    a1 = _run(data, dict(BASE_CONFIG), storage_path=str(tmp_path / "one"),
              resources_per_trial={"devices": 1})
    a4 = _run(data, dict(BASE_CONFIG), storage_path=str(tmp_path / "four"),
              resources_per_trial={"devices": 4})
    l1 = a1.trials[0].metric_history("validation_loss")
    l4 = a4.trials[0].metric_history("validation_loss")
    assert len(l1) == len(l4) == 4
    np.testing.assert_allclose(l1, l4, rtol=2e-4, atol=2e-6)


def test_tp_transformer_trial(data, tmp_path):
    """dp x tp mesh: transformer params actually sharded over tp."""
    config = {
        "model": "transformer",
        "d_model": 16,
        "num_heads": 2,
        "num_layers": 1,
        "dim_feedforward": 32,
        "dropout": 0.0,
        "max_seq_length": 16,
        "learning_rate": 0.01,
        "num_epochs": 2,
        "batch_size": 32,
        "lr_schedule": "constant",
        "mesh_shape": {"dp": 2, "tp": 2},
        "seed": 0,
    }
    analysis = _run(
        data, config, storage_path=str(tmp_path),
        resources_per_trial={"devices": 4},
    )
    t = analysis.trials[0]
    assert t.status == TrialStatus.TERMINATED
    assert t.training_iteration == 2
    assert all(np.isfinite(r["validation_loss"]) for r in t.results)


def test_tp_matches_dp_only_numerics(data, tmp_path):
    """TP sharding is a layout, not a numerics change: dp2xtp2 == dp4."""
    config = {
        "model": "transformer",
        "d_model": 16,
        "num_heads": 2,
        "num_layers": 1,
        "dim_feedforward": 32,
        "dropout": 0.0,
        "max_seq_length": 16,
        "learning_rate": 0.01,
        "num_epochs": 3,
        "batch_size": 32,
        "lr_schedule": "constant",
        "seed": 1,
    }
    a_tp = _run(data, {**config, "mesh_shape": {"dp": 2, "tp": 2}},
                storage_path=str(tmp_path / "tp"),
                resources_per_trial={"devices": 4})
    a_dp = _run(data, config, storage_path=str(tmp_path / "dp"),
                resources_per_trial={"devices": 4})
    np.testing.assert_allclose(
        a_tp.trials[0].metric_history("validation_loss"),
        a_dp.trials[0].metric_history("validation_loss"),
        rtol=5e-4, atol=5e-6,
    )


def test_sharded_checkpoint_restore_after_crash(data, tmp_path):
    """Fault path: a crashed multi-device trial restores sharded state."""
    train, val = data
    crash_marker = tmp_path / "crashed"

    def crashing(config, train_data=None, val_data=None):
        if not crash_marker.exists():
            crash_marker.write_text("1")
            # Run 2 epochs (reporting checkpoints), then die.
            cfg = dict(config, num_epochs=2)
            tune.train_sharded_regressor(
                cfg, train_data=train_data, val_data=val_data
            )
            raise RuntimeError("injected crash after epoch 2")
        tune.train_sharded_regressor(
            config, train_data=train_data, val_data=val_data
        )

    analysis = tune.run(
        tune.with_parameters(crashing, train_data=train, val_data=val),
        dict(BASE_CONFIG),
        metric="validation_loss",
        num_samples=1,
        max_failures=1,
        storage_path=str(tmp_path),
        resources_per_trial={"devices": 2},
        verbose=0,
    )
    t = analysis.trials[0]
    assert t.status == TrialStatus.TERMINATED
    assert t.num_failures == 1
    epochs = [r["epoch"] for r in t.results]
    # epochs 0,1 pre-crash; restore resumes at 2 (not 0)
    assert epochs[:2] == [0, 1]
    assert epochs[2] == 2 and epochs[-1] == 3


def test_resnet18_four_device_trial(tmp_path):
    """BASELINE.json config 5 verbatim: ResNet-18 regression head, one trial
    spanning 4 cores (dp-sharded batch; BatchNorm stats reduce across the
    shards under GSPMD)."""
    from distributed_machine_learning_tpu.data.loader import Dataset

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 16, 16, 3)).astype(np.float32)
    y = x.mean(axis=(1, 2, 3), keepdims=False)[:, None].astype(np.float32)
    train, val = Dataset(x[:96], y[:96]), Dataset(x[96:], y[96:])

    analysis = tune.run(
        tune.with_parameters(
            tune.train_sharded_regressor, train_data=train, val_data=val
        ),
        {
            "model": "resnet18",
            "learning_rate": 1e-3,
            "num_epochs": 2,
            "batch_size": 32,
            "lr_schedule": "constant",
            "seed": 0,
        },
        metric="validation_loss",
        num_samples=1,
        storage_path=str(tmp_path),
        resources_per_trial={"devices": 4},
        verbose=0,
    )
    t = analysis.trials[0]
    assert t.status == TrialStatus.TERMINATED
    assert t.last_result["num_devices"] == 4
    assert np.isfinite(t.last_result["validation_loss"])

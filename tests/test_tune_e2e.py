"""End-to-end HPO tests on the virtual 8-device CPU mesh.

Reproduces the reference smoke workload (`ray-tune-hpo-regression-sample.py`:
dummy sequence-regression data, small transformer, ASHA, best_config printed)
with zero Ray and zero torch — SURVEY.md §7's minimum slice.
"""

import numpy as np
import pytest

from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import dummy_regression_data
from distributed_machine_learning_tpu.tune.experiment import ExperimentAnalysis
from distributed_machine_learning_tpu.tune.trial import TrialStatus


@pytest.fixture(scope="module")
def small_data():
    return dummy_regression_data(num_samples=200, seq_len=12, num_features=6)


def _trainable(small_data):
    train, val = small_data
    return tune.with_parameters(tune.train_regressor, train_data=train, val_data=val)


SMOKE_SPACE = {
    "model": "mlp",
    "hidden_sizes": tune.choice([(32,), (32, 16)]),
    "learning_rate": tune.loguniform(1e-3, 1e-1),
    "weight_decay": tune.loguniform(1e-6, 1e-3),
    "num_epochs": 3,
    "batch_size": 32,
    "lr_schedule": "constant",
}


def test_single_trial_learns(small_data, tmp_results):
    analysis = tune.run(
        _trainable(small_data),
        {**SMOKE_SPACE, "learning_rate": 0.01, "hidden_sizes": (32, 16),
         "num_epochs": 8},
        metric="validation_loss",
        num_samples=1,
        storage_path=tmp_results,
        verbose=0,
    )
    trial = analysis.trials[0]
    assert trial.status == TrialStatus.TERMINATED
    assert trial.training_iteration == 8
    losses = trial.metric_history("validation_loss")
    assert losses[-1] < losses[0] * 0.8  # it actually learns
    # per-epoch stream has the structured fields (SURVEY.md §5)
    r = trial.last_result
    for key in ("epoch", "train_loss", "validation_mape", "lr",
                "training_iteration", "time_total_s"):
        assert key in r


def test_smoke_hpo_with_asha(small_data, tmp_results):
    analysis = tune.run(
        _trainable(small_data),
        SMOKE_SPACE,
        metric="validation_loss",
        mode="min",
        num_samples=8,
        scheduler=tune.ASHAScheduler(max_t=3, grace_period=1, reduction_factor=2),
        storage_path=tmp_results,
        name="smoke_asha",
        verbose=0,
    )
    assert analysis.num_terminated() == 8
    best = analysis.best_config
    assert best["learning_rate"] > 0
    # ASHA must have cut at least one trial before max_t
    iters = [t.training_iteration for t in analysis.trials]
    assert min(iters) < max(iters) or all(i == 3 for i in iters)
    # results persisted and reloadable
    reloaded = ExperimentAnalysis.from_directory(
        analysis.root, metric="validation_loss", mode="min"
    )
    assert reloaded.best_config["learning_rate"] == pytest.approx(
        best["learning_rate"]
    )


def test_concurrent_trials_use_multiple_devices(small_data, tmp_results):
    import jax

    assert len(jax.devices()) == 8  # conftest forced the virtual mesh
    analysis = tune.run(
        _trainable(small_data),
        {**SMOKE_SPACE, "num_epochs": 2},
        metric="validation_loss",
        num_samples=8,
        storage_path=tmp_results,
        name="concurrent",
        verbose=0,
    )
    assert analysis.num_terminated() == 8
    # overlapping wall-clock windows prove concurrency
    windows = [(t.started_at, t.finished_at) for t in analysis.trials]
    overlaps = sum(
        1 for i, (s1, e1) in enumerate(windows)
        for (s2, e2) in windows[i + 1:]
        if s1 < e2 and s2 < e1
    )
    assert overlaps > 0


def test_eight_trial_threads_on_one_device_match_one_at_a_time(
    small_data, tmp_results
):
    """Eight trial threads dispatching to ONE device with no lock of the
    framework's own between them: every trial reports what it reports
    when the same eight run one after the other."""
    import jax

    device = jax.devices()[0]

    def sweep(name, concurrent):
        return tune.run(
            _trainable(small_data),
            {**SMOKE_SPACE, "dropout": 0.1, "seed": tune.randint(0, 1000)},
            metric="validation_loss",
            num_samples=8,
            seed=5,
            devices=[device] * concurrent,
            max_concurrent=concurrent,
            storage_path=tmp_results,
            name=name,
            verbose=0,
        )

    together, alone = sweep("together", 8), sweep("alone", 1)
    assert together.num_terminated() == alone.num_terminated() == 8
    windows = [(t.started_at, t.finished_at) for t in together.trials]
    assert any(
        s1 < e2 and s2 < e1
        for i, (s1, e1) in enumerate(windows) for (s2, e2) in windows[i + 1:]
    )
    for a, b in zip(together.trials, alone.trials):
        assert a.config == b.config
        for metric in ("train_loss", "validation_loss", "lr"):
            assert a.metric_history(metric) == b.metric_history(metric)


def test_grid_search_enumerates_product(small_data, tmp_results):
    space = {
        **SMOKE_SPACE,
        "hidden_sizes": tune.choice([(16,), (32,)]),
        "model": "mlp",
        "learning_rate": 0.01,
        "num_epochs": 1,
        "batch_size": tune.choice([16, 32]),
    }
    analysis = tune.run(
        _trainable(small_data),
        space,
        metric="validation_loss",
        num_samples=100,  # searcher exhausts the grid first
        search_alg=tune.GridSearch(),
        storage_path=tmp_results,
        name="grid",
        verbose=0,
    )
    combos = {(tuple(t.config["hidden_sizes"]), t.config["batch_size"])
              for t in analysis.trials}
    assert len(analysis.trials) == 4
    assert len(combos) == 4


def test_bayesopt_improves_on_quadratic(tmp_results):
    # Pure function optimization: no model, direct report of f(x).
    def objective(config):
        x, y = config["x"], config["y"]
        val = (x - 0.3) ** 2 + (y - 0.7) ** 2
        tune.report({"f": val})

    analysis = tune.run(
        objective,
        {"x": tune.uniform(0.0, 1.0), "y": tune.uniform(0.0, 1.0)},
        metric="f",
        num_samples=30,
        search_alg=tune.BayesOptSearch(random_search_steps=8),
        storage_path=tmp_results,
        name="bo",
        verbose=0,
    )
    best = analysis.best_result["f"]
    assert best < 0.05  # random alone rarely gets this close in 30 draws; GP should
    # later suggestions should cluster near the optimum
    late = [t.config for t in analysis.trials[-10:]]
    dists = [abs(c["x"] - 0.3) + abs(c["y"] - 0.7) for c in late]
    assert min(dists) < 0.2


def test_trial_error_retry_and_report(small_data, tmp_results):
    calls = {"n": 0}

    def flaky(config):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        tune.report({"loss": 1.0})

    analysis = tune.run(
        flaky,
        {"lr": tune.uniform(0, 1)},
        metric="loss",
        num_samples=1,
        max_failures=1,
        storage_path=tmp_results,
        name="flaky",
        verbose=0,
    )
    assert analysis.trials[0].status == TrialStatus.TERMINATED
    assert analysis.trials[0].num_failures == 1

    def always_fails(config):
        raise RuntimeError("nope")

    analysis2 = tune.run(
        always_fails,
        {"lr": tune.uniform(0, 1)},
        metric="loss",
        num_samples=2,
        storage_path=tmp_results,
        name="failing",
        verbose=0,
    )
    assert all(t.status == TrialStatus.ERROR for t in analysis2.trials)
    assert "nope" in analysis2.trials[0].error


def test_baseline_config1_mlp_california_housing(tmp_path, monkeypatch):
    """BASELINE.json config 1 verbatim: MLP regression on California Housing
    (synthetic-tabular fallback), 4 trials on CPU devices. The sklearn
    download is blocked so the test is hermetic — no network, no retries,
    same data in every environment."""
    import sys

    from distributed_machine_learning_tpu.data import california_housing_data

    monkeypatch.setitem(sys.modules, "sklearn.datasets", None)
    train, val = california_housing_data()
    assert train.x.ndim == 2 and train.y.shape[1] == 1
    # Keep the smoke minute-scale: subsample.
    from distributed_machine_learning_tpu.data.loader import Dataset

    train = Dataset(train.x[:2000], train.y[:2000])
    val = Dataset(val.x[:500], val.y[:500])
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {
            "model": "mlp",
            "hidden_sizes": tune.choice([(32,), (64, 32)]),
            "learning_rate": tune.loguniform(1e-4, 1e-2),
            "num_epochs": 3,
            "batch_size": 64,
        },
        metric="validation_loss",
        mode="min",
        num_samples=4,
        storage_path=str(tmp_path),
        verbose=0,
    )
    assert analysis.num_terminated() == 4
    assert np.isfinite(analysis.best_result["validation_loss"])


def test_rng_impl_rbg_trains_and_resumes(tmp_path):
    """config rng_impl='rbg' (hardware-RNG dropout streams — the cheap
    path on TPU at sweep shapes) trains finitely through BOTH runners,
    and the vectorized population checkpoint round-trips rbg key data
    (wider than threefry's — wrap must use the same impl)."""
    from distributed_machine_learning_tpu.data import dummy_regression_data
    from distributed_machine_learning_tpu.tune.vectorized import run_vectorized

    train, val = dummy_regression_data(
        num_samples=96, seq_len=8, num_features=4
    )
    space = {
        "model": "simple_transformer", "d_model": 16, "num_heads": 2,
        "num_layers": 1, "dim_feedforward": 32, "dropout": 0.2,
        "learning_rate": 0.01, "seed": tune.randint(0, 1000),
        "num_epochs": 3, "batch_size": 32, "loss_function": "mse",
        "lr_schedule": "constant", "rng_impl": "rbg",
    }
    analysis = tune.run(
        tune.with_parameters(tune.train_regressor, train_data=train,
                             val_data=val),
        dict(space), metric="validation_mse", num_samples=1,
        storage_path=str(tmp_path / "run"), verbose=0,
    )
    assert np.isfinite(analysis.best_result["validation_mse"])

    # Vectorized, interrupted MID-SWEEP (simulated preemption at epoch 2 of
    # 3), then resumed: the continuation trains real epochs from restored
    # rbg keys — the impl-sensitive fold_in/train path after wrap_key_data.
    from distributed_machine_learning_tpu.tune.schedulers import FIFOScheduler

    class DiesAtEpoch(FIFOScheduler):
        def __init__(self, fatal_iteration):
            self.fatal_iteration = fatal_iteration

        def on_trial_result(self, trial, result):
            if result["training_iteration"] >= self.fatal_iteration:
                raise RuntimeError("simulated preemption")
            return super().on_trial_result(trial, result)

    with pytest.raises(RuntimeError, match="simulated preemption"):
        run_vectorized(
            dict(space), train_data=train, val_data=val,
            metric="validation_mse", mode="min", num_samples=2,
            storage_path=str(tmp_path), name="rbg_v", seed=3, verbose=0,
            checkpoint_every_epochs=1, scheduler=DiesAtEpoch(2),
        )
    v2 = run_vectorized(
        dict(space), train_data=train, val_data=val,
        metric="validation_mse", mode="min", num_samples=2,
        storage_path=str(tmp_path), name="rbg_v", seed=3, verbose=0,
        checkpoint_every_epochs=1, resume=True,
    )
    assert v2.num_terminated() == 2
    # Every trial reached full depth through the post-resume epochs.
    assert all(t.training_iteration == 3 for t in v2.trials)


def test_standalone_session_runs_trainable_directly():
    """tune.standalone(): a trainable runs OUTSIDE tune.run — reports are
    swallowed (always 'continue'), no checkpoint — the compile-warmup path
    bench.py's bohb variant uses before its concurrent cohort."""
    import numpy as np

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=64, seq_len=8, num_features=4
    )
    cfg = {
        "model": "simple_transformer", "d_model": 8, "num_heads": 2,
        "num_layers": 1, "dim_feedforward": 16, "learning_rate": 1e-3,
        "num_epochs": 2, "batch_size": 16, "loss_function": "mse",
    }
    with tune.standalone():
        # Completing both epochs without raising IS the contract (every
        # per-epoch report is swallowed with decision "continue").
        tune.train_regressor(cfg, train_data=train, val_data=val)
    # Outside the context the session is gone again.
    import pytest

    from distributed_machine_learning_tpu.tune import session

    with pytest.raises(RuntimeError):
        session.report({"x": 1.0})


def test_convention_probe_reraises_non_flag_errors():
    """A model whose init fails for a REAL reason (PE table shorter than
    the sequence) must surface that error, not a misleading
    "unexpected keyword argument 'train'" from the convention fallback
    (2026-08-01 refdata run forensics)."""
    import jax.numpy as jnp
    import pytest

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.tune._regression_program import (
        detect_call_convention,
    )

    model = build_model({
        "model": "transformer", "d_model": 16, "num_heads": 2,
        "num_layers": 1, "dim_feedforward": 32, "max_seq_length": 8,
    })
    x = jnp.zeros((1, 24, 4))  # seq 24 > PE table 8
    with pytest.raises(TypeError) as ei:
        detect_call_convention(model, x)
    assert "train" not in str(ei.value)

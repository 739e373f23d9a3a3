"""Compile-cache ownership (SURVEY.md §7 "hard parts": compile amortization).

The framework — not the user — enables JAX's persistent compilation cache and
accounts compile time per trial.  The decisive property: a second trial with
an identical architecture must HIT the cache (skip XLA backend compilation)
rather than pay the full compile again.
"""

import numpy as np
import pytest

from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import dummy_regression_data
from distributed_machine_learning_tpu.utils import compile_cache as cc


@pytest.fixture(scope="module")
def tiny_data():
    return dummy_regression_data(num_samples=120, seq_len=8, num_features=4)


def test_enable_persistent_cache_idempotent(tmp_path):
    d = str(tmp_path / "xc")
    assert cc.enable_persistent_cache(d) == d
    assert cc.enable_persistent_cache(d) == d
    assert cc.cache_dir() == d


def test_identical_arch_trials_hit_cache(tiny_data, tmp_path):
    """Trial #2 of an identical architecture reports ~zero backend compile.

    The trials run one after the other so trial 2's compile request can
    see trial 1's cache entries (concurrent compiles of the same program
    race and both miss).  Dropping the cohort's program cache between
    them pins the test to the PERSISTENT-cache layer: with it trial 2
    compiles (and traces) nothing at all, so there would be no cache
    lookup to observe — that stronger behavior has its own test
    (test_cohort_program_cache_builds_once_per_architecture).
    """
    train, val = tiny_data
    cache = str(tmp_path / "xla")

    def one_trial(name):
        analysis = tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            {
                "model": "mlp",
                "hidden_sizes": (16,),
                "learning_rate": tune.loguniform(1e-3, 1e-2),
                "num_epochs": 2,
                "batch_size": 32,
                "lr_schedule": "constant",
            },
            metric="validation_loss",
            num_samples=1,
            storage_path=str(tmp_path / "results"),
            name=name,
            compile_cache_dir=cache,
            verbose=0,
        )
        return analysis.trials[0].last_result

    tune.clear_cohort_program_cache()
    r1 = one_trial("first")
    assert cc.cache_entry_count() > 0  # programs landed on disk
    tune.clear_cohort_program_cache()
    r2 = one_trial("second")
    # compile accounting is stamped into every record
    assert "compile_time_s" in r1 and "compile_cache_hits" in r1
    assert r1["compile_time_s"] > 0
    # trial 2 traced the same program and hit the persistent cache
    assert r2["compile_cache_hits"] > 0
    assert r2["compile_time_s"] < r1["compile_time_s"]


def test_vectorized_records_compile_totals(tiny_data, tmp_path):
    train, val = tiny_data
    analysis = tune.run_vectorized(
        {
            "model": "mlp",
            "hidden_sizes": (16,),
            "learning_rate": tune.loguniform(1e-3, 1e-2),
            "num_epochs": 2,
            "batch_size": 32,
            "lr_schedule": "constant",
        },
        train_data=train,
        val_data=val,
        metric="validation_loss",
        num_samples=3,
        storage_path=str(tmp_path / "vresults"),
        compile_cache_dir=str(tmp_path / "vxla"),
        verbose=0,
    )
    import json, os

    state = json.load(open(os.path.join(analysis.root, "experiment_state.json")))
    assert state["compile_time_total_s"] > 0
    assert state["compile_cache_entries"] > 0

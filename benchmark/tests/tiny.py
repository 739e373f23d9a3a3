"""Tiny cells for the CPU: the same drivers, readers and checks as the
real cells, at sizes a test run can hold."""

import time

from benchmark.run import Cell, run_cell

TRAIN_LIMITS = {
    "loss_rerun_gap": 0.0, "param_change_half_ratio_med": 0.5,
    "param_change_gap_med": 1e-3, "param_change_gap_p90": 1e-3,
}


def train_cell(**traffic):
    t = {
        "driver": "train", "seq_len": 16, "batch_size": 8,
        "steps_per_epoch": 3, "val_windows": 8, "num_epochs": 1000,
        "trace_seconds": 1, "reference_block_rows": 4, "control": "bf16",
        "limits": dict(TRAIN_LIMITS),
    }
    t.update(traffic)
    return Cell(
        name="tiny_train", chips=1, config_name="tiny-top",
        config={"features": 4, "trial": {
            "model": "transformer", "d_model": 32, "num_heads": 4,
            "dim_feedforward": 64, "num_layers": 2, "dropout": 0.1,
            "optimizer": "adam", "loss_function": "mse",
            "learning_rate": 1e-3, "weight_decay": 1e-4,
        }},
        traffic_name="tiny", traffic=t,
        end_to_end=[{"name": "train_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[],
    )


def drive(cell, tmp_path, seed=3, seconds=0.5, traced=False):
    import jax

    return run_cell(
        cell, seed=seed, seconds=seconds, traced=traced,
        devices=jax.devices()[: cell.chips], work_dir=str(tmp_path),
        process_start=time.time(),
    )


SWEEP_LIMITS = {"first_epoch_gap_med": 1e-3,
                "first_epoch_train_gap_med": 1e-3, "asha_ends_moved": 0,
                "best_trial_differs": 0, "trials_not_ended": 0}


def sweep_cell(chips=1, **traffic):
    t = {
        "driver": "sweep", "population": 8, "max_t": 4, "grace_period": 1,
        "reduction_factor": 2, "seq_len": 12, "batch_size": 8,
        "steps_per_epoch": 2, "val_windows": 8, "trace_seconds": 1,
        "check_trials": 3, "check_first_epoch_trials": 3, "control": "bf16",
        "limits": dict(SWEEP_LIMITS),
    }
    t.update(traffic)
    return Cell(
        name="tiny_sweep", chips=chips, config_name="tiny-sweep",
        config={"features": 4, "trial": {
            "model": "transformer", "d_model": 16, "num_heads": 2,
            "dim_feedforward": 32, "num_layers": 1, "dropout": 0.1,
            "optimizer": "adam", "loss_function": "mse",
        }, "search": {
            "learning_rate": ["loguniform", 1e-4, 1e-2],
            "weight_decay": ["loguniform", 1e-6, 1e-3],
            "seed": ["randint", 0, 1000000],
        }},
        traffic_name="tiny", traffic=t,
        end_to_end=[{"name": "sweep_trials_per_s", "unit": "trials/s"},
                    {"name": "trial_done_p95_ms", "unit": "ms"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[],
    )

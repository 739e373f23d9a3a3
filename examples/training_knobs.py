"""Round-up of the training knobs: warm start, stoppers, mixed precision,
gradient accumulation, RoPE/GQA — one sweep using them all.

Run (CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/training_knobs.py

On TPU drop the overrides; set compute_dtype="bfloat16" for MXU-bound
model sizes (recorded 1.4-1.6x at d_model >= 512 in an earlier round, not measured on today's code;
tiny models are faster in f32).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_machine_learning_tpu import tune  # noqa: E402
from distributed_machine_learning_tpu.data import (  # noqa: E402
    dummy_regression_data,
)


def main():
    train, val = dummy_regression_data(
        num_samples=512, seq_len=24, num_features=8
    )
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {
            "model": "transformer",
            "d_model": tune.choice([16, 32]),
            "num_heads": 4,
            # GQA as a searchable knob: full MHA vs grouped vs multi-query.
            "num_kv_heads": tune.choice([1, 2, 4]),
            "num_layers": 2,
            "dim_feedforward": tune.sample_from(
                lambda cfg: cfg["d_model"] * 2
            ),
            # RoPE: relative positions, no max-length table.
            "position_encoding": "rope",
            "optimizer": tune.choice(["adamw", "lion"]),
            "learning_rate": tune.loguniform(1e-4, 1e-2),
            # 4x the effective batch at 1x the activation memory.
            "accumulate_grad_batches": 4,
            "num_epochs": 10,
            "batch_size": 16,
        },
        metric="validation_loss",
        mode="min",
        num_samples=8,
        # Known-good config runs first; the searcher learns from it.
        points_to_evaluate=[
            {"d_model": 32, "num_kv_heads": 4, "optimizer": "adamw",
             "learning_rate": 3e-3}
        ],
        # Converged trials stop early — scheduler-independent.
        stop=tune.TrialPlateauStopper(
            "validation_loss", std=1e-3, num_results=3, grace_period=3
        ),
        scheduler=tune.ASHAScheduler(
            max_t=10, grace_period=2, reduction_factor=2
        ),
        callbacks=[tune.TensorBoardCallback()],  # per-trial TB runs
        storage_path=os.environ.get("DML_RESULTS", "/tmp/dml_examples"),
        name="training_knobs",
        verbose=1,
    )
    print("best config:", {
        k: analysis.best_config[k]
        for k in ("d_model", "num_kv_heads", "optimizer", "learning_rate")
    })
    print("best validation_loss:",
          round(analysis.best_result["validation_loss"], 4))
    model, variables = analysis.best_model()
    preds = model.apply(variables, val.x[:4], deterministic=True)
    print("reloaded best model, preds:", preds.shape)


if __name__ == "__main__":
    main()

"""C1 interop, end to end: the full 20-hp driver on a REFERENCE-FORMAT file.

The reference trains from pickled ``{columns, data}`` ``.npy`` dumps with its
literal 81-column schema (`/root/reference/config.py:2-78`, loaded at
`ray-tune-hpo-regression.py:414-418`).  The real patient files are private,
so this script synthesizes a byte-compatible pair from raw sensor streams via
``build_feature_frame(schema="reference")`` — the reference's exact column
names, 9-window grid, and ``Is_Weekend`` flag — writes them exactly as the
reference stores its own, and then runs ``examples/hpo_full.py``'s driver on
them UNCHANGED (``get_dataset`` auto-detects the schema).  Proves a reference
user can point this framework at their existing data files and run the full
production sweep.

Bounded by default (12 trials x 4 epochs) to stay minute-scale; prints ONE JSON line with trials/hour + best config.

Run (CPU dev box):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/hpo_reference_data.py --num-samples 4 \
        --num-epochs 2 --rows-windows 24 --fast
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (the package)
sys.path.insert(0, _HERE)                   # examples/ (for `import hpo_full`)

def generate_reference_pair(out_dir: str, windows: int, patient: str) -> None:
    """Write ``<patient>_features.npy`` / ``<patient>_labels.npy`` in the
    reference's storage format (pickled {columns, data} dicts)."""
    import numpy as np
    import pandas as pd

    from distributed_machine_learning_tpu.data.features import (
        LABEL_COLUMN,
        build_feature_frame,
    )

    rows = 96 * windows  # one label window per 96 minutes (interval=96)
    rng = np.random.RandomState(11)
    idx = pd.date_range("2024-01-05 22:00", periods=rows, freq="min")
    raw = pd.DataFrame(
        {
            "heart_rate": 70 + 8 * rng.randn(rows),
            "sleep": (rng.rand(rows) > 0.6).astype(float),
            "intensity": rng.rand(rows) * 3,
            "steps": rng.poisson(5, rows).astype(float),
        },
        index=idx,
    )
    frame = build_feature_frame(raw, schema="reference")
    # Learnable target: a smooth function of the raw channels plus noise —
    # glucose-like positive values so validation_mape is well-behaved.
    hr = raw["heart_rate"].to_numpy()
    labels = pd.DataFrame({
        LABEL_COLUMN: (100.0 + 0.8 * (hr - 70.0)
                       + 6.0 * raw["intensity"].to_numpy()
                       + 2.0 * rng.randn(rows)).astype(np.float32)
    })

    os.makedirs(out_dir, exist_ok=True)
    for df, name in ((frame, "features"), (labels, "labels")):
        np.save(
            os.path.join(out_dir, f"{patient}_{name}.npy"),
            {"columns": list(df.columns),
             "data": df.to_numpy(dtype=np.float32)},
        )


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out-dir", default="/tmp/dml_reference_data")
    parser.add_argument("--patient", default="MMCS0002")
    parser.add_argument("--rows-windows", type=int, default=200,
                        help="number of 96-minute label windows to generate")
    parser.add_argument("--num-samples", type=int, default=12)
    parser.add_argument("--num-epochs", type=int, default=4)
    parser.add_argument("--storage", default="/tmp/dml_reference_results")
    parser.add_argument("--search", default="bayesopt",
                        choices=["bayesopt", "random", "tpe"])
    parser.add_argument("--fast", action="store_true",
                        help="shrink arch choices to minute-scale")
    args = parser.parse_args(argv)

    generate_reference_pair(args.out_dir, args.rows_windows, args.patient)
    features = os.path.join(args.out_dir, f"{args.patient}_features.npy")
    labels = os.path.join(args.out_dir, f"{args.patient}_labels.npy")

    import hpo_full

    t0 = time.time()
    analysis = hpo_full.main([
        "--features", features,
        "--labels", labels,
        "--num-samples", str(args.num_samples),
        "--num-epochs", str(args.num_epochs),
        "--storage", args.storage,
        "--search", args.search,
    ] + (["--fast"] if args.fast else []))
    wall = time.time() - t0

    import jax

    done = analysis.num_terminated()
    print(json.dumps({
        "metric": "hpo_full_reference_format_npy",
        "trials_per_hour": round(done * 3600.0 / wall, 2),
        "done": done,
        "wall_s": round(wall, 1),
        "backend": jax.devices()[0].platform,
        "best_validation_mape": analysis.best_result.get("validation_mape"),
        "best_config": {
            k: v for k, v in (analysis.best_config or {}).items()
            if isinstance(v, (int, float, str))
        },
        "data": {"features": features, "labels": labels,
                 "windows": args.rows_windows, "schema": "reference-81col"},
    }))
    return analysis


if __name__ == "__main__":
    main()

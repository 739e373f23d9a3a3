"""benchmark/run.py with the window's moe_stride_row_share printed after
the window closes (the result line leaves it out).
    python3 _archive/share_run.py --workload <cell> --seed <n> --seconds 30 --trace 0
"""
import importlib
import os
import sys

sys.path.insert(0, os.getcwd())
from benchmark import run as bench_run  # noqa: E402

for name in ("train_lm", "train_cca_lm", "train_swa_lm"):
    mod = importlib.import_module(f"benchmark.drivers.{name}")

    def window(run, state, orig=mod.window):
        orig(run, state)
        shares = [r.get("moe_stride_row_share") for r in state.window_records]
        print(f"[share] moe_stride_row_share by epoch: {shares}", flush=True)

    mod.window = window
sys.exit(bench_run.main(sys.argv[1:]))

"""Set-up and window of one cell with the program's spans recorded, the
check's programs not compiled and the check not run; prints every loaded
program's memory statistics after the window.  By hand:
    python3 _archive/diag_run.py <cell> <seed>
"""
import importlib
import json
import os
import sys
import tempfile
import time

T0 = time.time()
sys.path.insert(0, os.getcwd())


def main():
    cell_name, seed = sys.argv[1], int(sys.argv[2])
    import jax

    from benchmark.run import Cell, Run, load_json, HERE
    from distributed_machine_learning_tpu import compilecache, obs

    cell = Cell.load(os.getcwd(), cell_name)
    driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
    driver.compile_ahead = lambda *a, **k: None
    compilecache.enable_persistent_cache()
    tracer = obs.Tracer(buffer_limit=400_000)
    obs.install_tracer(tracer)
    devices = jax.devices()[:1]
    run = Run(cell=cell, seed=seed, seconds=30.0, traced=False, devices=devices,
              work_dir=tempfile.mkdtemp(prefix="dml_diag_"),
              peaks=load_json(HERE, "peaks.json")[devices[0].device_kind])
    state = driver.setup(run)
    print(f"[diag] set-up done at {time.time() - T0:.1f}s", flush=True)
    driver.window(run, state)
    print(f"[diag] window: {run.metrics}", flush=True)
    stats = devices[0].memory_stats()
    print("[diag] allocator", {k: stats[k] for k in ("peak_bytes_in_use", "bytes_in_use", "bytes_limit", "largest_free_block_bytes") if k in stats}, flush=True)
    for ex in devices[0].client.live_executables():
        try:
            m = ex.get_compiled_memory_stats()
            name = ex.hlo_modules()[0].name
        except Exception as exc:  # noqa: BLE001
            print("[diag] program without stats", exc); continue
        print(f"[diag] program {name}: code {m.generated_code_size_in_bytes/1e6:.1f} MB, "
              f"args {m.argument_size_in_bytes/1e9:.3f} out {m.output_size_in_bytes/1e9:.3f} "
              f"alias {m.alias_size_in_bytes/1e9:.3f} temp {m.temp_size_in_bytes/1e9:.3f} GB", flush=True)
    spans = {}
    t_first = min(r["ts"] for r in tracer.records())
    for r in sorted(tracer.records(), key=lambda r: r["ts"]):
        name = r.get("name")
        if name in ("trial.setup", "trial.build", "trial.stage_data", "trial.init_or_restore",
                    "run.setup", "run.teardown", "ckpt.save", "ckpt.write", "report",
                    "report.ckpt_snapshot", "experiment", "trial") or (name == "epoch" and len(spans.get("epoch", [])) < 4):
            spans.setdefault(name, []).append((round((r["ts"] - t_first) / 1e6, 1), round(r["dur"] / 1e6, 2)))
    print("[diag] spans (start s, seconds): " + json.dumps(spans), flush=True)


if __name__ == "__main__":
    main()

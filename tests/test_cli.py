"""Package CLI (`python -m distributed_machine_learning_tpu`)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=120):
    # The child imports the package from the repo root, on the CPU.
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    return subprocess.run(
        [sys.executable, "-m", "distributed_machine_learning_tpu"] + args,
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_info_prints_device_summary():
    proc = _run(["info"])
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["backend"] == "cpu"
    assert out["local_devices"] == 4
    assert out["process_count"] == 1


def test_help_and_unknown_command():
    proc = _run(["--help"], timeout=30)
    assert proc.returncode == 0
    assert "worker" in proc.stdout and "info" in proc.stdout
    proc = _run(["frobnicate"], timeout=30)
    assert proc.returncode == 2


def test_worker_help_forwards_to_cluster_cli():
    proc = _run(["worker", "--help"], timeout=30)
    assert proc.returncode == 0
    assert "--join" in proc.stdout and "--port" in proc.stdout


def test_export_orbax_subcommand(tmp_path):
    import numpy as np
    import pytest

    pytest.importorskip("orbax.checkpoint")  # optional dependency

    from distributed_machine_learning_tpu.tune.checkpoint import (
        checkpoint_path,
        save_checkpoint,
    )

    src = checkpoint_path(str(tmp_path), 1)
    save_checkpoint(src, {"params": {"w": np.ones(3)}})
    out_dir = str(tmp_path / "orbax_out")
    proc = _run(["export-orbax", src, out_dir])
    assert proc.returncode == 0, proc.stderr
    assert "exported" in proc.stdout
    assert os.path.isdir(out_dir)

    proc = _run(["export-orbax", "only-one-arg"], timeout=60)
    assert proc.returncode == 2


def test_export_orbax_friendly_errors(tmp_path):
    import pytest

    pytest.importorskip("orbax.checkpoint")
    # Missing checkpoint: one-line error, exit 1, no traceback.
    proc = _run(["export-orbax", str(tmp_path / "nope.msgpack"),
                 str(tmp_path / "o")], timeout=60)
    assert proc.returncode == 1
    assert "error:" in proc.stderr and "Traceback" not in proc.stderr


def test_probe_subcommand_cpu():
    """probe: bounded accelerator health check. On the CPU test platform it
    reports an executed computation and exits 1 (no accelerator)."""
    import json

    proc = _run(["probe", "--timeout", "90"])
    assert proc.returncode == 1, proc.stderr
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["platform"] == "cpu" and res["executed"] is True


def test_probe_times_out_on_wedged_backend():
    """A backend that hangs at init must yield exit 124 within the bound,
    not a hung shell (the failure mode bench.py's probe exists for)."""
    import json
    import subprocess

    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    # Wedge the child deterministically: a sitecustomize that sleeps at
    # interpreter start stands in for a backend that hangs at start-up.
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "sitecustomize.py"), "w") as f:
            # Sleep only in `python -c` children (the probe's worker), not
            # in the `-m` CLI parent that shares this PYTHONPATH.
            f.write(
                "import sys, time\n"
                "if sys.argv and sys.argv[0] == '-c':\n"
                "    time.sleep(120)\n"
            )
        env["PYTHONPATH"] = d + os.pathsep + REPO
        proc = subprocess.run(
            [sys.executable, "-m", "distributed_machine_learning_tpu",
             "probe", "--timeout", "5"],
            env=env, capture_output=True, text=True, timeout=120,
        )
    assert proc.returncode == 124, (proc.stdout, proc.stderr)
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "hung" in res["error"]


def test_probe_crashed_child_is_not_cpu_only():
    """A crashing probe child exits 2 — distinct from 'healthy CPU-only'
    (1), so pod-health scripts can't misread a broken env (code review
    r4)."""
    import subprocess
    import tempfile

    env = dict(os.environ)
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "sitecustomize.py"), "w") as f:
            # site.py swallows ordinary exceptions from sitecustomize;
            # os._exit reliably kills the child like a hard crash would.
            f.write(
                "import sys, os\n"
                "if sys.argv and sys.argv[0] == '-c':\n"
                "    sys.stderr.write('broken backend install\\n')\n"
                "    os._exit(17)\n"
            )
        env["PYTHONPATH"] = d + os.pathsep + REPO
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "distributed_machine_learning_tpu",
             "probe", "--timeout", "60"],
            env=env, capture_output=True, text=True, timeout=120,
        )
    assert proc.returncode == 2, (proc.stdout, proc.stderr)


def test_analyze_subcommand(tmp_path):
    """`analyze` rehydrates a finished experiment: recorded metric/mode are
    picked up from experiment_state.json, --json is machine-readable, and
    the human view prints the final table."""
    from distributed_machine_learning_tpu import tune

    def trainable(config):
        for _ in range(2):
            tune.report(loss=config["x"] ** 2)

    tune.run(
        trainable, {"x": tune.uniform(1.0, 2.0)},
        metric="loss", mode="min", num_samples=3,
        storage_path=str(tmp_path), name="cli_exp", verbose=0,
    )
    root = os.path.join(str(tmp_path), "cli_exp")

    proc = _run(["analyze", root, "--json"])
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["metric"] == "loss" and out["mode"] == "min"  # recorded
    assert out["num_terminated"] == 3
    assert 1.0 <= out["best_config"]["x"] <= 2.0
    assert "wall_clock_s" in out and "device_utilization" in out

    proc = _run(["analyze", root])
    assert proc.returncode == 0, proc.stderr
    assert "Final result" in proc.stdout
    assert "best loss:" in proc.stdout
    assert "best config:" in proc.stdout
    # Progress/runtime restored from the state file, not zeroed.
    for line in proc.stdout.splitlines():
        if line.strip().startswith("trial_"):
            cols = line.split()
            assert cols[2] == "2", line   # iter column: 2 reports

    # Typo'd PATH is diagnosed first — never "pass --metric" advice.
    proc = _run(["analyze", str(tmp_path / "nope")])
    assert proc.returncode == 1
    assert "no experiment directory" in proc.stderr

    # Typo'd METRIC errors in both output modes (exit 0 with an all-dash
    # table would pass scripted `analyze && ...` checks silently).
    for extra in (["--json"], []):
        proc = _run(["analyze", root, "--metric", "typo_metric"] + extra)
        assert proc.returncode == 1, extra
        assert "typo_metric" in proc.stderr
        assert "Traceback" not in proc.stderr


def test_loop_status_subcommand(tmp_path):
    """`loop status` reads a journal (file or out_dir), prints the
    episode trail + counters, flags open episodes, and --json emits the
    raw documents; unreadable paths get a one-liner, not a stack dump."""
    doc = {
        "episode": 2, "state": "retraining", "trace_id": "abc123",
        "data": {"warm_start": "/ckpts/gen_0007"},
        "history": [
            {"state": "detected", "at_unix": 100.0},
            {"state": "retraining", "at_unix": 101.5,
             "warm_start": "/ckpts/gen_0007"},
        ],
        "completed_episodes": 1, "promotions": 1, "rollbacks": 0,
    }
    with open(tmp_path / "loop.json", "w") as f:
        json.dump(doc, f)
    with open(tmp_path / "experiment_state.json", "w") as f:
        json.dump({"loop": {"episodes": 2, "promotions": 1,
                            "rollbacks": 0, "resumes": 1,
                            "gate_rejects": 0, "aborts": 0}}, f)

    # Directory form resolves to <dir>/loop.json.
    proc = _run(["loop", "status", str(tmp_path)], timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "episode 2: retraining" in proc.stdout
    assert "OPEN" in proc.stdout          # non-terminal -> resume hint
    assert "abc123" in proc.stdout
    assert "warm_start=/ckpts/gen_0007" in proc.stdout
    assert "resumes=1" in proc.stdout

    # --json round-trips both documents.
    proc = _run(["loop", "status", str(tmp_path / "loop.json"), "--json"],
                timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["journal"]["state"] == "retraining"
    assert out["counters"]["resumes"] == 1

    proc = _run(["loop", "status", str(tmp_path / "missing.json")],
                timeout=60)
    assert proc.returncode == 1
    assert "cannot read journal" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_store_subcommand(tmp_path):
    """`store {stats,verify,gc}`: dedup-aware stats, dry-run-by-default
    GC (nothing deleted until --run), and verify exiting 1 with the
    corrupt digest named."""
    from distributed_machine_learning_tpu import store as store_lib

    root = str(tmp_path / ".cas")
    cas = store_lib.get_store(root)
    keep = cas.put_blob(b"keep me" * 64)
    cas.put_blob(b"keep me" * 64)  # dedup hit, no new blob
    cas.put_blob(b"drop me" * 64)  # never referenced -> GC fodder
    manifest = cas.put_manifest({
        "kind": "demo",
        store_lib.MANIFEST_CHUNKS_KEY: [keep],
    })
    cas.set_ref("demo-ref", manifest)

    proc = _run(["store", "stats", root, "--json"], timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["blobs"] == 3  # keep + drop + the manifest blob
    assert out["refs"] == 1

    # A served directory resolves to its .cas sibling, same as writers.
    proc = _run(["store", "stats", str(tmp_path), "--json"], timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["root"].endswith(".cas")

    # GC defaults to a dry run: reports the unreachable blob, deletes
    # nothing.
    proc = _run(["store", "gc", root, "--json"], timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["dry_run"] is True
    assert out["collected"] == 1 and out["retained"] == 2
    assert json.loads(_run(["store", "stats", root, "--json"],
                           timeout=60).stdout)["blobs"] == 3

    proc = _run(["store", "gc", root, "--run", "--json"], timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout)
    assert out["dry_run"] is False and out["collected"] == 1
    assert json.loads(_run(["store", "stats", root, "--json"],
                           timeout=60).stdout)["blobs"] == 2

    proc = _run(["store", "verify", root], timeout=60)
    assert proc.returncode == 0, proc.stderr

    # Bit-rot a live blob: verify names the digest and exits 1.
    blob_path = os.path.join(root, "blobs", keep[:2], keep)
    with open(blob_path, "wb") as f:
        f.write(b"rotten")
    proc = _run(["store", "verify", root], timeout=60)
    assert proc.returncode == 1
    assert keep in proc.stdout
    assert "Traceback" not in proc.stderr

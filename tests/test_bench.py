"""bench.py: the parent (runs the suite child once, never imports jax,
fails without a TPU) and the ``child_*`` measurement bodies at tiny size
on the CPU, called directly.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import pytest

_BENCH_PY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench.py"
)
spec = importlib.util.spec_from_file_location("bench", _BENCH_PY)
bench = importlib.util.module_from_spec(spec)
sys.modules.setdefault("bench", bench)
spec.loader.exec_module(bench)


def test_parse_result_takes_last_json_line():
    out = "noise\n{\"a\": 1}\nmore noise\n{\"b\": 2}\n"
    assert bench._parse_result(out) == {"b": 2}
    assert bench._parse_result("no json at all") is None
    assert bench._parse_result("{broken\n") is None


def test_variant_scales_cover_baseline_configs():
    assert set(bench.VARIANT_SCALES) == {
        "pbt_cnn", "bohb_transformer", "sharded_resnet"
    }
    for name, scales in bench.VARIANT_SCALES.items():
        assert set(scales) == {"full", "small"}, name


def test_variant_partial_recovers_terminated_trials(tmp_path, monkeypatch):
    """A dead variant child's experiment_state.json yields a flagged
    partial result; nothing-terminated and no-experiment-dir yield None."""
    import time

    monkeypatch.setattr(bench, "BENCH_RESULTS_DIR", str(tmp_path))
    exp = "variant_bohb_transformer_test"
    root = tmp_path / exp
    root.mkdir(parents=True)
    t_start = time.time() - 120.0
    state = {
        "timestamp": t_start + 100.0,
        "trials": [
            {"trial_id": "a", "status": "TERMINATED",
             "last_result": {"validation_mse": 3.5}},
            {"trial_id": "b", "status": "TERMINATED",
             "last_result": {"validation_mse": 2.25}},
            {"trial_id": "c", "status": "RUNNING",
             "last_result": {"validation_mse": 0.1}},
        ],
    }
    (root / "experiment_state.json").write_text(json.dumps(state))
    res = bench._variant_partial("bohb_transformer", exp, t_start)
    assert res["partial"] is True
    assert res["done"] == 2
    assert abs(res["trials_per_hour"] - 2 * 36.0) < 0.5  # 2 per 100s
    assert res["platform"] == "tpu"
    assert res["best_validation_mse"] == 2.25  # running trial's 0.1 excluded

    state["trials"] = [{"trial_id": "a", "status": "RUNNING"}]
    (root / "experiment_state.json").write_text(json.dumps(state))
    assert bench._variant_partial("bohb_transformer", exp, t_start) is None
    # No experiment dir at all (child died before tune.run created it).
    assert bench._variant_partial("bohb_transformer", "absent", t_start) is None


def test_child_suite_end_to_end_tiny(monkeypatch, tmp_path, capsys):
    """child_suite for real at tiny shapes on CPU: one process produces
    flagship + both-dtype sweeps, checkpoints the partial, heartbeats —
    and a second (resume) invocation skips every completed phase."""
    monkeypatch.setattr(bench, "FLAGSHIP", dict(
        d_model=16, num_heads=2, num_layers=1, dim_feedforward=32,
        seq=16, batch=2, features=4,
    ))
    monkeypatch.setattr(bench, "SMALL", dict(
        num_trials=2, num_epochs=1, data_steps=10_000, warm_repeats=0,
    ))
    partial = tmp_path / "suite.json"
    hb = tmp_path / "hb"
    monkeypatch.setenv("DML_BENCH_PARTIAL_PATH", str(partial))
    monkeypatch.setenv("DML_BENCH_HEARTBEAT_PATH", str(hb))
    bench.child_suite("small")
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out["sweeps"]) == {"float32", "bfloat16"}
    # Every result names the device it ran on.
    assert out["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    assert out["flagship"].get("step_s"), out["flagship"]
    for res in out["sweeps"].values():
        assert res["trials_per_hour"] > 0 and res["done"] == 2
    assert hb.exists() and partial.exists()
    saved = json.loads(partial.read_text())
    assert set(saved["sweeps"]) == {"float32", "bfloat16"}

    # Resume run: every phase already in the partial -> all skipped, the
    # printed suite is identical (no recomputation).
    bench.child_suite("small")
    out2 = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out2["sweeps"]["float32"]["trials_per_hour"] == (
        out["sweeps"]["float32"]["trials_per_hour"]
    )
    assert out2["flagship"]["step_s"] == out["flagship"]["step_s"]


def test_child_serve_soak_end_to_end_tiny(monkeypatch, capsys):
    """child_serve_soak for real (tiny request count): sustained RPS
    against a 2-replica continuous-batching server, a chaos kill and a
    hot swap mid-soak — zero dropped (non-shed) requests, zero post-swap
    recompiles, both events counter-verified in the emitted section."""
    monkeypatch.setenv("DML_SOAK_REQUESTS", "60")
    monkeypatch.setenv("DML_SOAK_RPS", "60")
    monkeypatch.setenv("DML_SOAK_BURST_RPS", "150")
    bench.child_serve_soak()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["requests"] == 60
    assert out["dropped"] == 0
    assert out["ok"] + out["shed"] == 60
    # The kill landed and the set HEALED — whether the monitor restart or
    # the hot swap won the race for the dead slot is timing, not contract
    # (the deterministic restart proof is test_replica_failover_and_restart).
    assert out["replica_kills"] == 1
    assert out["replicas_healthy"] == out["replicas_final"] >= 1
    assert out["hot_swap_signals"] == 1 and out["swap_landed"] is True
    assert out["swaps_total"] == 1
    assert out["post_swap_new_programs"] == 0
    assert out["p99_ms"] >= out["p50_ms"] > 0
    assert out["achieved_rps"] > 0
    assert out["trajectory"], "replica-count trajectory must be recorded"
    # ISSUE 16: precision arms ride beside the soak — f32 and int8 of the
    # same architecture on identical clean servers, each number tagged
    # with a precision-keyed comparability class.
    assert out["precision"] == "f32"
    assert out["comparability"] == "cpu-f32"
    arms = out["precision_arms"]
    assert set(arms) == {"f32", "int8"}
    for p, arm in arms.items():
        assert arm["precision"] == p
        assert arm["comparability"] == f"cpu-{p}"
        assert arm["rps_per_replica"] > 0
        assert arm["p99_ms"] > 0
        assert arm["new_programs_since_warmup"] == 0


def test_child_flagship_tiny_shapes(monkeypatch, capsys):
    """child_flagship end-to-end at tiny shapes on CPU: prints incremental
    JSON (MHA -> +GQA -> +batch_x2), the closure rebinding doubles the
    batch for the scaling variant, and no-peak platforms skip promotion."""
    monkeypatch.setattr(bench, "FLAGSHIP", dict(
        d_model=16, num_heads=2, num_layers=1, dim_feedforward=32,
        seq=16, batch=2, features=4,
    ))
    bench.child_flagship()
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    # MHA, +gqa, +seq_x2, +tile_256, +pre-XL checkpoint, final(complete)
    # — crash-safe increments.
    assert len(lines) == 6
    final = json.loads(lines[-1])
    assert final["xl_d1024"] == {"skipped": "cpu"}
    assert final["config"]["batch"] == 2  # no promotion without peak flops
    assert final["gqa_kv2"].get("step_s") or final["gqa_kv2"].get("error")
    bx2 = final["batch_x2"]
    assert bx2.get("batch") == 4 or bx2.get("error")  # closure saw 2*B
    sx2 = final["seq_x2"]
    assert sx2.get("seq") == 32 or sx2.get("error")  # measured at 2*S


def test_child_flagship_promotes_winning_batch(monkeypatch, capsys):
    """The promotion branch: when the doubled batch wins MFU, every shared
    per-run field AND the config's batch move to the winner together."""
    monkeypatch.setattr(bench, "FLAGSHIP", dict(
        d_model=16, num_heads=2, num_layers=1, dim_feedforward=32,
        seq=16, batch=2, features=4,
    ))
    # CPU has no peak-flops table: stub one so mfu is computed, making the
    # larger batch (better amortized overhead) eligible to win.
    monkeypatch.setattr(
        "distributed_machine_learning_tpu.ops.flops.device_peak_flops",
        lambda device, compute_dtype=None: 1e12,
    )
    bench.child_flagship()
    final = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    bx2 = final["batch_x2"]
    assert "error" not in bx2, bx2
    assert final["mfu"] is not None and bx2["mfu"] is not None
    if bx2["mfu"] > final.get("gqa_kv2", {}).get("mfu", 0) or True:
        # Whichever run won, the headline fields must be mutually
        # consistent: step_s implies the flops and mfu of the SAME run.
        assert final["mfu"] == pytest.approx(
            final["flops_per_step"] / final["step_s"] / 1e12, abs=1e-4
        )  # 1e-4 = measure()'s rounding granularity for the mfu field
        winner = bx2 if bx2["mfu"] > final["mfu"] else final
        if winner is bx2:
            assert final["config"]["batch"] == 4
            assert final["compile_plus_first_step_s"] == (
                bx2["compile_plus_first_step_s"]
            )
        if final["config"]["batch"] >= 4:
            # x2 won -> the climb must have attempted the x4 doubling
            # (measured or recorded its error) before settling.
            assert "batch_x4" in final


def test_run_variant_monitored_with_partial_recovery(monkeypatch, tmp_path,
                                                     capsys):
    """The TPU variant child runs under heartbeat monitoring; a stale-kill
    (rc=124) still yields the terminated trials from the experiment state
    as a flagged partial, and the exit code stays the child's."""
    import time as _time

    monkeypatch.setattr(bench, "BENCH_RESULTS_DIR", str(tmp_path))
    seen = {}

    def fake_monitored(args, env, timeout_s, hb_path, stale_s):
        assert args == ["--child", "variant", "bohb_transformer", "full"]
        assert env["DML_BENCH_HEARTBEAT_PATH"] == hb_path
        seen["stale_s"] = stale_s
        exp = env["DML_BENCH_EXP_NAME"]
        root = tmp_path / exp
        root.mkdir(parents=True)
        (root / "experiment_state.json").write_text(json.dumps({
            "timestamp": _time.time(),
            "trials": [
                {"trial_id": "a", "status": "TERMINATED",
                 "last_result": {"validation_mse": 1.5}},
                {"trial_id": "b", "status": "RUNNING"},
            ],
        }))
        return 124, "", "heartbeat stale"

    monkeypatch.setattr(bench, "_run_child_monitored", fake_monitored)
    assert bench.run_variant("bohb_transformer") == 124
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["partial"] is True
    assert line["done"] == 1
    assert line["best_validation_mse"] == 1.5
    assert seen["stale_s"] == bench.HEARTBEAT_STALE_S


def test_monitored_runner_kills_stale_real_process(tmp_path):
    """End-to-end staleness kill on a REAL child process: the child beats
    once then hangs; the monitored parent must SIGTERM it shortly after
    the heartbeat goes stale — minutes before the wall timeout."""
    import time as _time

    hb = str(tmp_path / "hb")
    env = dict(os.environ, DML_BENCH_HEARTBEAT_PATH=hb)
    t0 = _time.time()
    rc, out, err = bench._run_child_monitored(
        ["--child", "_test_stall"], env, 120, hb, 3.0
    )
    elapsed = _time.time() - t0
    assert rc == 124
    assert elapsed < 60, elapsed  # killed at staleness, not the timeout


def test_child_suite_reruns_incomplete_flagship(monkeypatch, tmp_path,
                                                capsys):
    """Advisor r4: a flagship snapshot killed mid-sub-phase (no 'complete'
    marker, no 'error') must be RE-RUN by the resume child, not skipped —
    the GQA/batch-climb evidence is recoverable."""
    monkeypatch.setattr(bench, "FLAGSHIP", dict(
        d_model=16, num_heads=2, num_layers=1, dim_feedforward=32,
        seq=16, batch=2, features=4,
    ))
    monkeypatch.setattr(bench, "SMALL", dict(
        num_trials=2, num_epochs=1, data_steps=10_000, warm_repeats=0,
    ))
    partial = tmp_path / "suite.json"
    partial.write_text(json.dumps({
        "flagship": {"step_s": 0.5, "platform": "cpu"},  # no 'complete'
        "sweeps": {
            "float32": {"trials_per_hour": 111.0, "wall_s": 1.0,
                        "done": 2, "flops": 1.0, "platform": "cpu",
                        "compute_dtype": "float32", "peak_flops": None},
            "bfloat16": {"trials_per_hour": 222.0, "wall_s": 1.0,
                         "done": 2, "flops": 1.0, "platform": "cpu",
                         "compute_dtype": "bfloat16", "peak_flops": None},
        },
    }))
    monkeypatch.setenv("DML_BENCH_PARTIAL_PATH", str(partial))
    monkeypatch.setenv("DML_BENCH_HEARTBEAT_PATH", str(tmp_path / "hb"))
    bench.child_suite("small")
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # Sweeps were kept (no re-run), the flagship was re-measured fully.
    assert out["sweeps"]["float32"]["trials_per_hour"] == 111.0
    assert out["flagship"].get("complete") is True
    assert out["flagship"]["step_s"] != 0.5
    assert "gqa_kv2" in out["flagship"]


def test_monitored_runner_retains_full_child_logs(tmp_path, monkeypatch):
    """DML_BENCH_CHILD_LOG_DIR keeps the child's FULL stdout/stderr
    (pid-stamped): the 2026-08-01 bohb stall was undiagnosable because
    only the stderr tail survived the run."""
    hb = str(tmp_path / "hb")
    env = dict(os.environ, DML_BENCH_HEARTBEAT_PATH=hb)
    log_dir = tmp_path / "children"
    monkeypatch.setenv("DML_BENCH_CHILD_LOG_DIR", str(log_dir))
    rc, out, err = bench._run_child_monitored(
        ["--child", "_test_stall"], env, 120, hb, 3.0
    )
    assert rc == 124
    outs = sorted(log_dir.glob("*.out"))
    errs = sorted(log_dir.glob("*.err"))
    assert len(outs) == 1 and len(errs) == 1, list(log_dir.iterdir())
    # pid-stamped (same-second same-args children must not clobber) and
    # rc recorded in the name; contents are the child's full streams.
    assert "_pid" in outs[0].name and outs[0].name.endswith("_rc124.out")
    assert outs[0].read_text() == out
    assert errs[0].read_text() == err


def test_child_streaming_end_to_end_tiny(monkeypatch, capsys):
    """child_streaming for real (tiny dataset): the same workload trained
    resident then through the prefetch ring under a virtual budget the
    dataset exceeds — over-budget proven, streaming engaged, params
    bit-identical, overlap counters behind the ratio."""
    monkeypatch.setenv("DML_STREAM_SAMPLES", "600")
    monkeypatch.setenv("DML_STREAM_EPOCHS", "2")
    monkeypatch.setenv("DML_STREAM_BUDGET_BYTES", str(256 << 10))
    bench.child_streaming()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["resident_over_budget"] is True
    assert out["streamed"] is True
    assert out["params_bit_identical"] is True
    assert out["chunks_staged"] > 0 and out["bytes_staged"] > 0
    assert out["resident_step_s"] > 0 and out["streaming_step_s"] > 0
    assert out["step_rate_vs_resident"] > 0
    # pass_0p9 is the bench ACCEPTANCE on real runs; at this toy size the
    # ratio is noisy, so assert it is derived consistently, not its value.
    assert out["pass_0p9"] == (out["step_rate_vs_resident"] >= 0.9)


def test_child_online_loop_end_to_end_tiny(capsys):
    """child_online_loop for real: the served model drifts, the monitor
    triggers once, the journaled episode promotes a retrained candidate,
    and the recovery claims are counter-verified — zero dropped requests,
    zero serving-path compiles."""
    bench.child_online_loop()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["state"] == "promoted"
    assert out["drift_triggers"] == 1 and out["promotions"] == 1
    assert out["recovered"] is True
    assert out["healed_mape"] < out["drifted_mape"]
    assert out["dropped"] == 0
    assert out["post_swap_new_programs"] == 0
    assert out["detect_s"] >= 0 and out["heal_s"] > 0


def test_child_head_recovery_end_to_end_tiny(capsys):
    """child_head_recovery for real: a sweep's head is killed mid-
    journal-append, auto-resume finishes it, and the emitted timings
    carry the counter-verified crash-equals-control claim."""
    bench.child_head_recovery()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["best_matches_control"] is True
    assert out["committed"] is True
    assert out["head_incarnations"] == 2
    assert out["detect_s"] >= 0 and out["replay_s"] >= 0
    assert out["resume_total_s"] > 0


def test_child_store_end_to_end_tiny(capsys, monkeypatch):
    """child_store for real: the generation chain + PBT exploits dedup
    past the <0.5x acceptance bar, and the ref-copy export moves zero
    parameter-chunk bytes."""
    monkeypatch.delenv("DML_STORE_ROOT", raising=False)
    bench.child_store()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["pass_half"] is True
    assert out["bytes_physical"] < 0.5 * out["bytes_logical"]
    assert out["dedup_hits"] > 0 and out["pbt_dedup_hits"] > 0
    assert out["export_param_blob_writes"] == 0
    assert out["export_chunks"] == 2  # w + b
    assert out["export_refcopy_s"] >= 0




# -- the parent ---------------------------------------------------------------


def test_main_runs_the_suite_child_once_and_relays_its_line(monkeypatch,
                                                            capsys):
    calls = []
    suite = {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
             "sweeps": {"float32": {"trials_per_hour": 1.0}}}

    def fake_monitored(args, env, timeout_s, hb_path, stale_s):
        calls.append(args)
        assert env["DML_BENCH_HEARTBEAT_PATH"] == hb_path
        return 0, "narration\n" + json.dumps(suite) + "\n", ""

    monkeypatch.setattr(bench, "_run_child_monitored", fake_monitored)
    assert bench.main() == 0
    assert calls == [["--child", "suite", "full"]]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["device"] == suite["device"]
    assert line["sweeps"] == suite["sweeps"]


@pytest.mark.parametrize("rc,out", [(3, ""), (124, ""), (0, "no json here")])
def test_main_fails_without_a_line_when_the_child_fails(monkeypatch, capsys,
                                                        rc, out):
    monkeypatch.setattr(
        bench, "_run_child_monitored",
        lambda *a: (rc, out, "child said why"),
    )
    assert bench.main() != 0
    assert capsys.readouterr().out.strip() == ""


def test_require_tpu_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        bench._require_tpu()
    assert exc.value.code == 3
    assert "needs a TPU" in capsys.readouterr().err


def test_parent_imports_no_jax_and_fails_without_a_tpu():
    """``python bench.py`` for real on this CPU host: non-zero, no metric
    line; and importing the module pulls in no jax."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys, importlib.util as u\n"
         f"s = u.spec_from_file_location('bench', {_BENCH_PY!r})\n"
         "m = u.module_from_spec(s); s.loader.exec_module(m)\n"
         "assert 'jax' not in sys.modules, 'parent imported jax'\n"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert probe.returncode == 0, probe.stderr
    proc = subprocess.run(
        [sys.executable, _BENCH_PY], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_no_scaffolding_left():
    with open(_BENCH_PY) as f:
        src = f.read()
    for gone in ("FALLBACK_PEAK_FLOPS", "_probe_tpu", "PROBE_SCHEDULE",
                 "os.execve", "LAST_TPU_CAPTURE_PATH", "_run_tpu_suite",
                 "INTER_CHILD_GAP_S", "mkdtemp(prefix=\"dml_bench_xla_\""):
        assert gone not in src, gone

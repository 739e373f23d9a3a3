"""perf/ performance observatory (ISSUE 15).

The contracts under test:

* **cost-model audit** — ``compiled.cost_analysis()`` captured by the
  AOT cache with ZERO extra compiles (counter-verified: one
  ``program_misses`` across capture + sidecar reload), parity of the
  analytic FLOP model against XLA's count on the known model families
  (tolerance asserted in BOTH directions), and the cross-check catching
  a seeded analytic understatement;
* **anomaly detection** — median/MAD robust z-scores; a sustained slow
  outlier increments registry counters NAMING the culprit
  (``perf_straggler[<who>]``) and triggers a flight dump; gang-skew
  naming by process id;
* **regression sentinel** — goldens over five synthetic rounds: exactly
  one comparable chain (the chip round), the CPU rounds flagged
  cpu-fallback/non-comparable, no false regression — and a synthetic
  in-class regression does exit the gate nonzero;
* **straggler e2e** — a chaos-slowed producer on ONE trial of a
  streaming sweep is named (trial id) in the anomaly counters and the
  triggered flight dump.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu import chaos, obs, perf, tune
from distributed_machine_learning_tpu.compilecache import (
    get_counters as get_compile_counters,
)
from distributed_machine_learning_tpu.compilecache.aot import (
    ExecutableCache,
)
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.ops.flops import (
    epoch_flops,
    forward_flops,
    train_step_flops,
)
from distributed_machine_learning_tpu.perf.anomaly import (
    GangSkewMonitor,
    RobustWindow,
    StepAnomalyDetector,
)
from distributed_machine_learning_tpu.tune.trial import TrialStatus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeTpu:
    """A duck-typed v5e device: enough for the peak/bandwidth tables."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"bytes_in_use": 123456}


# ---------------------------------------------------------------------------
# cost capture + sidecars: zero extra compiles
# ---------------------------------------------------------------------------


def test_cost_captured_with_zero_extra_compiles(tmp_path):
    """The audit rides ONLY executables the AOT cache was compiling (or
    deserializing) anyway: one miss total across first compile + fresh-
    instance reload, sidecar written once and REUSED on reload."""
    counters = get_compile_counters()
    base = counters.snapshot()
    key = "pk_perf_zero_compile"
    perf.reset_cost_store()

    def fn(x, w):
        return x @ w

    args = (jnp.ones((8, 16), jnp.float32), jnp.ones((16, 4), jnp.float32))
    store = ExecutableCache(str(tmp_path))
    store.get_or_compile(key, fn, *args)
    d = counters.delta_since(base)
    assert d["program_misses"] == 1
    assert d["cost_captures"] == 1
    cost = perf.program_cost(key)
    assert cost is not None and cost["flops"] > 0
    assert os.path.exists(perf.cost_sidecar_path(str(tmp_path), key))

    # Fresh instance (= restarted process): executable deserialized, cost
    # re-read from the sidecar — no new compile, no new cost derivation.
    perf.reset_cost_store()
    store2 = ExecutableCache(str(tmp_path))
    store2.get_or_compile(key, fn, *args)
    d = counters.delta_since(base)
    assert d["program_misses"] == 1  # ZERO extra compiles
    assert d["aot_imports"] == 1
    assert d["cost_captures"] == 1  # not re-derived
    assert d["cost_sidecar_loads"] == 1
    reloaded = perf.program_cost(key)
    assert reloaded is not None
    assert reloaded["flops"] == cost["flops"]


def test_extract_cost_matches_matmul_exactly():
    def f(x, w):
        return x @ w

    compiled = jax.jit(f).lower(
        jnp.ones((32, 64)), jnp.ones((64, 16))
    ).compile()
    cost = perf.extract_cost(compiled)
    assert cost is not None
    assert cost["flops"] == pytest.approx(2 * 32 * 64 * 16)
    assert cost["bytes_accessed"] > 0


def test_extract_cost_absorbs_missing_analysis():
    class _NoCost:
        def cost_analysis(self):
            raise RuntimeError("backend has no cost analysis")

    assert perf.extract_cost(_NoCost()) is None


# ---------------------------------------------------------------------------
# analytic parity goldens (tolerance asserted BOTH directions)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,batch,seq,feats", [
    ("mlp", 8, 16, 4),
    ("simple_transformer", 8, 16, 4),
    ("transformer", 4, 12, 4),
])
def test_analytic_forward_flops_parity_with_xla(family, batch, seq, feats):
    """The analytic model may be slightly conservative (matmul-only) but
    must track XLA's count: measured/analytic within [0.95, 1.25] — the
    lower bound catches an analytic OVERstatement, the upper an
    UNDERstatement (the GQA/remat bug class)."""
    config = {"model": family, "dropout": 0.0}
    x = np.zeros((batch, seq, feats), np.float32)
    if family == "mlp":
        x = x.reshape(batch, seq * feats)
    model = build_model(config)
    variables = model.init(jax.random.key(0), x)

    def apply(v, xin):
        return model.apply(v, xin, deterministic=True)

    compiled = jax.jit(apply).lower(variables, x).compile()
    measured = perf.extract_cost(compiled)["flops"]
    analytic = forward_flops(config, batch, seq, feats)
    ratio = measured / analytic
    assert 0.95 <= ratio <= 1.25, (
        f"{family}: measured {measured:g} vs analytic {analytic:g} "
        f"({ratio:.3f}x)"
    )


def test_crosscheck_catches_seeded_understatement():
    """Acceptance fixture: an analytic model that forgot 2/3 of the work
    (the pre-advisor-r3 remat/GQA bug class) must be reported."""
    reg = obs.get_registry()
    base = reg.counters_snapshot()
    measured = 9e12
    finding = perf.crosscheck(measured / 3.0, measured, label="fixture")
    assert finding is not None
    assert finding["kind"] == "analytic-understates"
    assert finding["ratio"] == pytest.approx(3.0)
    delta = reg.delta_since(base)
    assert delta.get("perf_costmodel_checks", 0) == 1
    assert delta.get("perf_costmodel_divergences", 0) == 1
    # ... and the symmetric direction is caught too.
    over = perf.crosscheck(measured * 3.0, measured, label="fixture")
    assert over is not None and over["kind"] == "analytic-overstates"
    # Within tolerance: silent.
    assert perf.crosscheck(measured, measured * 1.2) is None


def test_crosscheck_program_via_recorded_cost():
    perf.reset_cost_store()

    class _Fixture:
        def cost_analysis(self):
            return [{"flops": 6e9, "bytes accessed": 1e6}]

    perf.record_program_cost("pk_fixture_model", _Fixture())
    finding = perf.crosscheck_program("pk_fixture_model", 6e9 / 4)
    assert finding is not None
    assert finding["kind"] == "analytic-understates"
    assert perf.crosscheck_program("pk_absent", 1.0) is None


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


def test_roofline_classification():
    peak, bw = 197e12, 819e9  # v5e
    ridge = peak / bw  # ~240 flops/byte
    compute = perf.roofline(
        {"flops": 1e12, "bytes_accessed": 1e9}, peak, bw
    )  # intensity 1000
    assert compute["bound"] == "compute"
    memory = perf.roofline(
        {"flops": 1e10, "bytes_accessed": 1e9}, peak, bw
    )  # intensity 10
    assert memory["bound"] == "memory"
    assert memory["ridge_intensity"] == pytest.approx(ridge, rel=0.01)
    assert perf.roofline(None, peak, bw) is None
    assert perf.roofline({"flops": 1e10}, peak, None) is None


def test_device_tables_for_fake_v5e():
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
    )

    dev = _FakeTpu()
    assert device_peak_flops(dev, "bfloat16") == pytest.approx(197e12)
    assert perf.device_hbm_bandwidth(dev) == pytest.approx(819e9)
    assert perf.device_hbm_bandwidth(None) is None


# ---------------------------------------------------------------------------
# EpochPerfAccounting: the one shared MFU helper
# ---------------------------------------------------------------------------


def _mlp_config():
    return {"model": "mlp", "hidden_sizes": (16,), "batch_size": 32}


def test_epoch_accounting_keys_byte_compatible_on_tpu_device():
    cfg = _mlp_config()
    acct = perf.EpochPerfAccounting(
        cfg, batch_size=32, seq_len=8, features=6, steps_per_epoch=4,
        eval_rows=40, device=_FakeTpu(), trial_id="trial_keys",
    )
    record = {"epoch": 0}
    acct.annotate(record, exec_s=0.123456789, device=_FakeTpu())
    # EXACTLY the keys + rounding the trainables used to compute inline.
    expected_flops = epoch_flops(cfg, 32, 8, 6, 4, 40)
    assert record["epoch_time_s"] == round(0.123456789, 4)
    assert record["device_bytes_in_use"] == 123456
    assert record["epoch_flops"] == expected_flops
    peak = 197e12 / 2  # fp32 on v5e
    assert record["mfu"] == round(expected_flops / 0.123456789 / peak, 5)
    assert "roofline_bound" not in record  # no captured program cost


def test_epoch_accounting_cpu_omits_mfu():
    record = {}
    acct = perf.EpochPerfAccounting(
        _mlp_config(), batch_size=32, seq_len=8, features=6,
        steps_per_epoch=4, eval_rows=40, device=jax.devices()[0],
    )
    acct.annotate(record, exec_s=0.05)
    assert record["epoch_time_s"] == 0.05
    assert "mfu" not in record  # CPU: no known peak
    assert "roofline_bound" not in record


def test_epoch_accounting_reports_roofline_and_crosscheck():
    """With a captured program cost + a known device, records carry
    ``roofline_bound`` and a seeded understatement is caught at
    construction."""
    perf.reset_cost_store()
    cfg = _mlp_config()
    analytic_step = train_step_flops(cfg, 32, 8, 6)

    class _Fixture:
        def cost_analysis(self):
            # 4x the analytic program's work, very low intensity.
            return [{
                "flops": analytic_step * 4 * 4.0,
                "bytes accessed": analytic_step * 4 * 100.0,
            }]

    perf.record_program_cost("pk_epoch_fixture", _Fixture())
    acct = perf.EpochPerfAccounting(
        cfg, batch_size=32, seq_len=8, features=6, steps_per_epoch=4,
        eval_rows=0, device=_FakeTpu(),
        program_key="pk_epoch_fixture",
    )
    assert acct.crosscheck_finding is not None
    assert acct.crosscheck_finding["kind"] == "analytic-understates"
    record = {}
    acct.annotate(record, exec_s=0.01)
    assert record["roofline_bound"] == "memory"


# ---------------------------------------------------------------------------
# anomaly detection
# ---------------------------------------------------------------------------


def test_robust_window_zscore():
    w = RobustWindow(capacity=16)
    for v in (0.1, 0.1, 0.11, 0.1, 0.09, 0.1):
        w.add(v)
    assert w.zscore(0.1) == pytest.approx(0.0, abs=1.0)
    assert w.zscore(0.5) > 10.0  # a 5x step is a screaming outlier
    fresh = RobustWindow()
    fresh.add(0.1)
    assert fresh.zscore(0.5) is None  # below MIN_SAMPLES: no judgment


def test_sustained_anomaly_names_culprit_and_dumps(tmp_path):
    obs.set_dump_dir(str(tmp_path))
    try:
        reg = obs.get_registry()
        base = reg.counters_snapshot()
        det = StepAnomalyDetector(z_threshold=4.0, sustain=3)
        for _ in range(12):
            det.observe("prog/a", 0.1, who="trial_fast")
        last = None
        for _ in range(3):
            last = det.observe("prog/a", 0.6, who="trial_slow")
        assert last is not None and last["sustained"]
        assert last["who"] == "trial_slow"
        delta = reg.delta_since(base)
        assert delta.get("perf_anomaly_events", 0) >= 3
        assert delta.get("perf_anomaly_sustained", 0) == 1
        # The culprit is named IN the counter, not just the dump.
        assert delta.get("perf_straggler[trial_slow]", 0) == 1
        dumps = glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
        assert dumps, "sustained anomaly must trigger a flight dump"
        payload = json.load(open(sorted(dumps)[-1]))
        assert payload["extra"]["who"] == "trial_slow"
        assert payload["extra"]["program"] == "prog/a"
    finally:
        obs.set_dump_dir(None)


def test_fast_outliers_are_not_anomalies():
    det = StepAnomalyDetector(sustain=2)
    for _ in range(10):
        det.observe("prog/fast", 0.2)
    assert det.observe("prog/fast", 0.01) is None  # fast, not a straggler


def test_gang_skew_names_process_id(tmp_path):
    obs.set_dump_dir(str(tmp_path))
    try:
        reg = obs.get_registry()
        base = reg.counters_snapshot()
        assert perf.skew_by_member({0: 0.1, 1: 0.1, 2: 0.35}) == [
            (2, 3.5)
        ]
        assert perf.skew_by_member({0: 0.1, 1: 0.1, 2: 0.12}) == []
        mon = GangSkewMonitor(ratio_threshold=1.75, sustain=2,
                              gang_id="g1")
        mon.observe_round({0: 0.1, 1: 0.1, 2: 0.4})
        stragglers = mon.observe_round({0: 0.1, 1: 0.1, 2: 0.4})
        assert stragglers and stragglers[0][0] == 2
        delta = reg.delta_since(base)
        assert delta.get("perf_straggler[process_2]", 0) == 1
        dumps = glob.glob(os.path.join(str(tmp_path), "flightrec_*.json"))
        assert dumps
        payload = json.load(open(sorted(dumps)[-1]))
        assert payload["extra"]["process_id"] == 2
        assert payload["extra"]["gang_id"] == "g1"
    finally:
        obs.set_dump_dir(None)


def test_skew_streak_resets_on_healthy_round():
    mon = GangSkewMonitor(ratio_threshold=1.75, sustain=2)
    mon.observe_round({0: 0.1, 1: 0.4}, report=False)
    mon.observe_round({0: 0.1, 1: 0.1}, report=False)  # healthy: reset
    mon.observe_round({0: 0.1, 1: 0.4}, report=False)
    snap = mon.snapshot()
    assert snap["rounds"] == 3
    assert snap["straggler_rounds"] == 2
    assert mon._streaks.get(1) == 1  # streak restarted, not sustained


# ---------------------------------------------------------------------------
# regression sentinel: goldens over synthetic rounds
# ---------------------------------------------------------------------------


def _bench_round(tmp_path, n, parsed):
    path = os.path.join(str(tmp_path), f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"n": n, "parsed": parsed}, f)
    return path


def _multichip_round(tmp_path, n, ok=True, n_devices=8):
    path = os.path.join(str(tmp_path), f"MULTICHIP_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"ok": ok, "rc": 0 if ok else 1,
                   "n_devices": n_devices}, f)
    return path


def _five_rounds(tmp_path):
    """Five synthetic rounds in the shape the sentinel was built for: two
    that printed nothing parseable, one chip capture, and two CPU
    captures that must never read as chip regressions."""
    cpu = {"metric": "trials_per_hour", "unit": "trials/h",
           "backend": "cpu", "compute_dtype": "float32"}
    return [
        _bench_round(tmp_path, 1, None),
        _bench_round(tmp_path, 2, {
            "metric": "trials_per_hour", "value": 5000.0,
            "unit": "trials/h", "backend": "tpu",
        }),
        _bench_round(tmp_path, 3, dict(cpu, value=722.64)),
        _bench_round(tmp_path, 4, None),
        _bench_round(tmp_path, 5, dict(cpu, value=1372.46)),
    ] + [_multichip_round(tmp_path, n) for n in range(1, 6)]


def test_sentinel_golden_over_five_rounds(tmp_path):
    """ISSUE 15 acceptance: exactly ONE comparable chain (the chip round),
    the CPU rounds flagged cpu-fallback/non-comparable, NO false
    regression."""
    rounds = perf.load_rounds(_five_rounds(tmp_path))
    report = perf.evaluate_rounds(rounds)
    assert report["reference_backend"] == "tpu"
    assert len(report["comparable_chains"]) == 1
    chain = report["comparable_chains"][0]
    assert chain["backend"] == "tpu"
    assert chain["rounds"] == [2]  # the chip capture
    fallback = {fb["round"]: fb for fb in report["fallback_rounds"]}
    assert set(fallback) == {3, 5}  # r04 is unparsed, not mis-bucketed
    for fb in fallback.values():
        assert fb["comparability"].startswith("cpu-fallback vs tpu")
    # The same-backend delta is informational — r03->r05 is an
    # IMPROVEMENT on cpu, reported as such but never a chip verdict.
    assert fallback[5]["vs_prev_same_backend"] == pytest.approx(
        1372.46 / 722.64, rel=0.01
    )
    assert report["unparsed_rounds"] == [1, 4]
    assert report["regressions"] == []
    assert report["ok"] is True
    # Render must not throw and must carry the verdict line.
    text = perf.render_report(report)
    assert "no in-class regression" in text


def test_sentinel_flags_in_class_regression(tmp_path):
    paths = [
        _bench_round(tmp_path, 1, {
            "metric": "m", "value": 1000.0, "unit": "u",
            "backend": "tpu", "compute_dtype": "bfloat16",
        }),
        _bench_round(tmp_path, 2, {
            "metric": "m", "value": 600.0, "unit": "u",
            "backend": "tpu", "compute_dtype": "bfloat16",
        }),
    ]
    report = perf.evaluate_rounds(perf.load_rounds(paths))
    assert report["ok"] is False
    (reg,) = report["regressions"]
    assert reg["from_round"] == 1 and reg["to_round"] == 2
    assert reg["ratio"] == pytest.approx(0.6)
    # Within the noise band: flat, ok.
    paths[1] = _bench_round(tmp_path, 2, {
        "metric": "m", "value": 950.0, "unit": "u",
        "backend": "tpu", "compute_dtype": "bfloat16",
    })
    report = perf.evaluate_rounds(perf.load_rounds(paths))
    assert report["ok"] is True
    assert report["verdicts"][0]["verdict"] == "flat"


def test_sentinel_dtype_change_is_non_comparable(tmp_path):
    """A compute-dtype flip on the same backend splits the class: the
    verdict is non-comparable, never a regression."""
    paths = [
        _bench_round(tmp_path, 1, {
            "metric": "m", "value": 1000.0, "unit": "u",
            "backend": "tpu", "compute_dtype": "float32",
        }),
        _bench_round(tmp_path, 2, {
            "metric": "m", "value": 500.0, "unit": "u",
            "backend": "tpu", "compute_dtype": "bfloat16",
        }),
    ]
    report = perf.evaluate_rounds(perf.load_rounds(paths))
    assert report["ok"] is True
    assert report["verdicts"][0]["verdict"] == "non-comparable"
    assert len(report["comparable_chains"]) == 2


def test_perf_compare_cli_gate(tmp_path):
    """The CI smoke gate: exit 0 over a set of round artifacts, human
    report on stdout."""
    _five_rounds(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_machine_learning_tpu",
         "perf", "compare", "--artifacts",
         os.path.join(str(tmp_path), "BENCH_r*.json"),
         os.path.join(str(tmp_path), "MULTICHIP_r*.json")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "cpu-fallback vs tpu" in proc.stdout
    assert "no in-class regression" in proc.stdout


def test_perf_compare_cli_exits_nonzero_on_regression(tmp_path):
    _bench_round(tmp_path, 1, {
        "metric": "m", "value": 1000.0, "unit": "u", "backend": "cpu",
        "compute_dtype": "float32",
    })
    _bench_round(tmp_path, 2, {
        "metric": "m", "value": 500.0, "unit": "u", "backend": "cpu",
        "compute_dtype": "float32",
    })
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_machine_learning_tpu",
         "perf", "compare", "--json", "--artifacts",
         os.path.join(str(tmp_path), "BENCH_r*.json")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout
    report = json.loads(proc.stdout)
    # All-cpu artifact set: nothing chip-era to defer to, so the cpu
    # rounds ARE the comparable chain and an in-class drop is real.
    assert report["reference_backend"] is None
    assert report["regressions"]


# ---------------------------------------------------------------------------
# straggler e2e: chaos-slowed producer named in counters + dump
# ---------------------------------------------------------------------------


def test_chaos_slowed_trial_named_in_counters_and_dump(tmp_results,
                                                      tmp_path):
    """ISSUE 15 acceptance: ONE trial of a streaming sweep runs with a
    chaos-slowed producer; the anomaly plane must name THAT trial in the
    registry counters and in the triggered flight-recorder dump."""
    from distributed_machine_learning_tpu.data import (
        dummy_regression_data,
    )
    from distributed_machine_learning_tpu.perf.anomaly import (
        get_step_anomalies,
    )

    train, val = dummy_regression_data(
        num_samples=128, seq_len=8, num_features=6
    )
    det = get_step_anomalies()
    det.reset()
    reg = obs.get_registry()
    base = reg.counters_snapshot()
    dump_dir = str(tmp_path / "dumps")
    os.makedirs(dump_dir)
    obs.set_dump_dir(dump_dir)
    # 60ms per chunk x 4 chunks/epoch vs ~ms-scale clean epochs: the
    # slowed trial's wall is an order of magnitude out.  sustain=3 fires
    # within its 8 epochs; peers fill the shared program-class window.
    plan = chaos.FaultPlan(
        seed=7, slow_producer_ms=60,
        slow_producer_match=("stream-trial_00001",),
    )
    try:
        with chaos.active(plan):
            analysis = tune.run(
                tune.with_parameters(
                    tune.train_regressor, train_data=train, val_data=val
                ),
                {
                    "model": "mlp", "hidden_sizes": (16,),
                    "learning_rate": tune.loguniform(1e-3, 1e-2),
                    "batch_size": 32, "num_epochs": 8,
                    "lr_schedule": "constant",
                    "input_mode": "streaming",
                    "streaming_chunk_batches": 1,
                },
                metric="validation_loss",
                num_samples=3,
                max_concurrent=1,  # deterministic trial order
                storage_path=tmp_results,
                name="perf_straggler_e2e",
                verbose=0,
            )
    finally:
        obs.set_dump_dir(None)
    assert analysis.num_terminated() == 3
    assert all(
        t.status == TrialStatus.TERMINATED for t in analysis.trials
    )
    # Only the targeted trial's producer slept.
    assert plan.snapshot()["producer_slowdowns"] > 0
    delta = reg.delta_since(base)
    assert delta.get("perf_anomaly_sustained", 0) >= 1
    # The culprit is NAMED in the counters...
    assert delta.get("perf_straggler[trial_00001]", 0) >= 1
    named = [
        k for k, v in delta.items()
        if k.startswith("perf_straggler[") and v
    ]
    assert named == ["perf_straggler[trial_00001]"]  # and ONLY it
    # ... and in the flight dump (the driver repoints the process dump
    # dir at the experiment root, which is where operators look).
    dumps = sorted(
        glob.glob(os.path.join(dump_dir, "flightrec_*.json"))
        + glob.glob(os.path.join(analysis.root, "flightrec_*.json"))
    )
    assert dumps, "sustained straggler must trigger a flight dump"
    named_dumps = [
        p for p in dumps
        if json.load(open(p)).get("extra", {}).get("who")
        == "trial_00001"
    ]
    assert named_dumps, "the dump must name the slowed trial"

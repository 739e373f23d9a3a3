"""quant/: post-training bf16/int8 quantization — calibrated export,
dequant-fused serving programs through the AOT executable cache, and
audited promotion (hot swap f32 -> int8 with zero drops, zero compiles).

ISSUE 16 acceptance rides here: an int8 bundle exported from a real
sweep serves through a ReplicaSet with no uncached compiles after warm,
survives a mid-traffic hot swap, and its manifest-recorded quality delta
bounds what the served predictions actually do.
"""

import json
import os
import threading

import jax
import numpy as np
import pytest

from distributed_machine_learning_tpu import quant, serve, tune
from distributed_machine_learning_tpu.compilecache import aot as aot_lib
from distributed_machine_learning_tpu.compilecache import counters as cc
from distributed_machine_learning_tpu.data import dummy_regression_data


@pytest.fixture(scope="module")
def experiment(tmp_path_factory):
    """One tiny finished experiment shared by the quantization tests;
    returns (analysis, val_data) — same shape as test_serve's fixture."""
    tmp = str(tmp_path_factory.mktemp("quant_exp"))
    train, val = dummy_regression_data(
        num_samples=96, seq_len=6, num_features=4, seed=7
    )
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {"model": "mlp", "hidden_sizes": [16],
         "learning_rate": tune.loguniform(1e-3, 1e-2),
         "num_epochs": 2, "batch_size": 32, "seed": 5},
        metric="validation_loss", mode="min", num_samples=2,
        storage_path=tmp, name="quant_src", verbose=0,
    )
    return analysis, val


@pytest.fixture(scope="module")
def calibration(experiment):
    _, val = experiment
    return np.asarray(val.x[:16], np.float32)


@pytest.fixture(scope="module")
def f32_bundle_dir(experiment, tmp_path_factory):
    analysis, _ = experiment
    out = str(tmp_path_factory.mktemp("quant_bundles") / "f32")
    serve.export_bundle(analysis, out)
    return out


@pytest.fixture(scope="module")
def int8_bundle_dir(experiment, calibration, tmp_path_factory):
    analysis, _ = experiment
    out = str(tmp_path_factory.mktemp("quant_bundles") / "int8")
    serve.export_bundle(
        analysis, out, precision="int8", calibration_batch=calibration
    )
    return out


def _mape(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.mean(np.abs(a - b) / (np.abs(b) + 1e-8)))


# --------------------------------------------------------------------------
# core: quantize / dequantize
# --------------------------------------------------------------------------


def test_quantize_leaf_roundtrip_bounded_by_scale():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    q, scale = quant.quantize_leaf(w)
    assert q.dtype == np.int8 and q.shape == w.shape
    # Symmetric per-channel: one scale per output channel, broadcastable.
    assert scale.shape == (1, 32)
    assert int(np.abs(q).max()) <= 127
    back = np.asarray(q, np.float32) * np.asarray(scale, np.float32)
    # Rounding error is at most half a step per element.
    assert np.all(np.abs(back - w) <= np.asarray(scale) / 2 + 1e-7)


def test_quantize_params_skips_sub2d_leaves():
    rng = np.random.default_rng(1)
    params = {
        "Dense_0": {
            "kernel": rng.normal(size=(8, 4)).astype(np.float32),
            "bias": rng.normal(size=(4,)).astype(np.float32),
        }
    }
    qparams, scales, stats = quant.quantize_params(params, "int8")
    assert qparams["Dense_0"]["kernel"].dtype == np.int8
    # Biases (and any sub-2-d leaf) stay f32 — rounding them buys no
    # bytes and costs exactly where it hurts.
    assert qparams["Dense_0"]["bias"].dtype == np.float32
    assert "kernel" in scales["Dense_0"] and "bias" not in scales["Dense_0"]
    assert stats["quantized_leaves"] == 1 and stats["total_leaves"] == 2
    assert stats["compression"] > 1.0


def test_bf16_precision_casts_without_scales():
    rng = np.random.default_rng(2)
    params = {"kernel": rng.normal(size=(8, 4)).astype(np.float32),
              "bias": rng.normal(size=(4,)).astype(np.float32)}
    qparams, scales, stats = quant.quantize_params(params, "bf16")
    assert str(qparams["kernel"].dtype) == "bfloat16"
    assert str(qparams["bias"].dtype) == "bfloat16"
    assert scales == {}
    assert stats["method"] == "cast"


def test_check_precision_rejects_unknown():
    with pytest.raises(ValueError, match="precision"):
        quant.check_precision("fp4")


def test_dequantize_params_raises_on_missing_scale():
    q = {"kernel": np.zeros((4, 4), np.int8)}
    with pytest.raises(ValueError, match="scale"):
        quant.dequantize_params(q, {})


def test_fake_quant_population_rounds_per_row():
    rng = np.random.default_rng(3)
    # Leading axis = population rows; each row quantizes independently.
    params = {"kernel": rng.normal(size=(4, 8, 6)).astype(np.float32),
              "bias": rng.normal(size=(4, 6)).astype(np.float32)}
    fq = quant.fake_quant_population(params)
    assert fq["kernel"].dtype == np.float32  # f32 in, f32 out
    assert np.array_equal(fq["bias"], params["bias"])  # sub-matrix: passthrough
    err = np.abs(np.asarray(fq["kernel"]) - params["kernel"])
    assert 0 < err.max() < 0.05  # rounded, but int8-close
    # Rows quantize independently: zeroing row 0 must not change row 1.
    params2 = {k: v.copy() for k, v in params.items()}
    params2["kernel"][0] = 0.0
    fq2 = quant.fake_quant_population(params2)
    np.testing.assert_array_equal(
        np.asarray(fq2["kernel"])[1], np.asarray(fq["kernel"])[1]
    )


def test_quantize_variables_roundtrip_tree_precision():
    rng = np.random.default_rng(4)
    variables = {"params": {
        "Dense_0": {"kernel": rng.normal(size=(16, 8)).astype(np.float32),
                    "bias": np.zeros((8,), np.float32)},
    }}
    qvars, stats = quant.quantize_variables(variables, "int8")
    assert quant.tree_precision(qvars) == "int8"
    assert "quant_scales" in qvars
    fvars = quant.dequantize_variables(qvars, "int8")
    assert "quant_scales" not in fvars
    k = np.asarray(fvars["params"]["Dense_0"]["kernel"], np.float32)
    assert np.abs(k - variables["params"]["Dense_0"]["kernel"]).max() < 0.05


# --------------------------------------------------------------------------
# export: manifest precision + calibration
# --------------------------------------------------------------------------


def test_manifest_always_records_precision(f32_bundle_dir):
    """Every export records its precision — f32 included — so a mixed
    fleet is diagnosable from manifests alone."""
    with open(os.path.join(f32_bundle_dir, "bundle.json")) as f:
        manifest = json.load(f)
    assert manifest["precision"] == "f32"
    bundle = serve.load_bundle(f32_bundle_dir)
    assert bundle.precision == "f32"
    assert bundle.quality_delta_mape is None


def test_int8_export_manifest_is_audited(int8_bundle_dir, calibration):
    bundle = serve.load_bundle(int8_bundle_dir)
    assert bundle.precision == "int8"
    assert quant.tree_precision(bundle.variables) == "int8"
    q = bundle.manifest["quant"]
    # The calibration audit: measured quality delta + the batch that
    # measured it + the per-leaf scale digest + the byte win.
    assert q["calibration"]["batch_size"] == len(calibration)
    assert bundle.quality_delta_mape is not None
    assert 0 <= bundle.quality_delta_mape < 0.2
    assert q["compression"] > 1.5
    assert q["quantized_leaves"] >= 1
    assert q["scales"], "per-leaf scale digest must ride in the manifest"


def test_int8_export_requires_calibration_batch(
    experiment, tmp_path
):
    analysis, _ = experiment
    with pytest.raises(ValueError, match="calibration"):
        serve.export_bundle(
            analysis, str(tmp_path / "nocal"), precision="int8"
        )


def test_quantize_bundle_writes_audited_sibling(
    f32_bundle_dir, calibration, tmp_path
):
    out = quant.quantize_bundle(
        f32_bundle_dir, str(tmp_path / "sibling_int8"), "int8", calibration
    )
    sib = serve.load_bundle(out)
    assert sib.precision == "int8"
    assert sib.manifest["source"]["parent_bundle"] == f32_bundle_dir
    assert sib.quality_delta_mape is not None
    # Quantizing a quantized bundle is refused — deltas don't compose.
    with pytest.raises(ValueError, match="quantiz"):
        quant.quantize_bundle(
            out, str(tmp_path / "twice"), "int8", calibration
        )


# --------------------------------------------------------------------------
# serving: dequant-fused programs, bounded quality, AOT restart
# --------------------------------------------------------------------------


def test_int8_predict_within_manifest_delta(
    f32_bundle_dir, int8_bundle_dir, calibration
):
    """The e2e quality contract: the served int8 predictions on the
    calibration batch stay within the manifest's recorded delta (margin
    for the serving path's padding/fusion differences vs the eager
    calibration pass)."""
    b32 = serve.load_bundle(f32_bundle_dir)
    b8 = serve.load_bundle(int8_bundle_dir)
    e32 = serve.InferenceEngine(b32, max_bucket=16, persistent_cache=False)
    e8 = serve.InferenceEngine(b8, max_bucket=16, persistent_cache=False)
    assert e8.precision == "int8"
    assert e8.program_stats()["precision"] == "int8"
    f = e32.predict(calibration)
    q = e8.predict(calibration)
    # The one f32 upcast (quant.dequantize_output) makes the client
    # answer f32 regardless of storage precision.
    assert f.dtype == q.dtype == np.float32
    delta = b8.quality_delta_mape
    assert _mape(q, f) <= delta * 1.5 + 1e-3


def test_restarted_replica_imports_int8_programs_without_compiling(
    int8_bundle_dir, calibration, tmp_path
):
    """The zero-compile restart story holds for quantized programs: a
    fresh engine over the same AOT directory deserializes every int8
    bucket program — zero program misses, only imports."""
    bundle = serve.load_bundle(int8_bundle_dir)
    e1 = serve.InferenceEngine(
        bundle, max_bucket=8, persistent_cache=False, aot_cache=False
    )
    e1._aot = aot_lib.ExecutableCache(str(tmp_path))
    base = cc.get_counters().snapshot()
    e1.warmup(calibration[:4])
    warm = cc.get_counters().delta_since(base)
    assert warm["program_misses"] >= 1
    assert warm["aot_exports"] >= 1

    # Capability gate: some jaxlib CPU builds emit fusion symbols that are
    # not relocatable across executables ("Symbols not found" on
    # deserialize); reload then falls back to a recompile by design, so the
    # zero-miss restart claim is unverifiable there. Probe-reload one of
    # the programs e1 actually exported before asserting strictly.
    exported = sorted(
        f[: -len(".aotexec")]
        for f in os.listdir(tmp_path)
        if f.endswith(".aotexec")
    )
    assert exported, "warmup exported no programs"
    if aot_lib.ExecutableCache(str(tmp_path))._load_from_disk(
        exported[0], [jax.devices()[0]]
    ) is None:
        pytest.skip("backend cannot deserialize its exported bucket programs")

    # "Restart": a brand-new engine, same bundle, same AOT directory.
    e2 = serve.InferenceEngine(
        bundle, max_bucket=8, persistent_cache=False, aot_cache=False
    )
    e2._aot = aot_lib.ExecutableCache(str(tmp_path))
    base = cc.get_counters().snapshot()
    e2.warmup(calibration[:4])
    restart = cc.get_counters().delta_since(base)
    assert restart["program_misses"] == 0, restart
    assert restart["aot_imports"] >= 1
    x = calibration[:4]
    np.testing.assert_array_equal(e1.predict(x), e2.predict(x))


def test_int8_programs_get_cost_sidecars_and_roofline(
    int8_bundle_dir, calibration, tmp_path
):
    """Perf-observatory audit (ISSUE 15 integration): the int8 programs'
    XLA cost records ride the AOT cache as ``<key>.cost.json`` sidecars
    and classify under the roofline like any other program."""
    from distributed_machine_learning_tpu.perf import costmodel

    bundle = serve.load_bundle(int8_bundle_dir)
    eng = serve.InferenceEngine(
        bundle, max_bucket=8, persistent_cache=False, aot_cache=False
    )
    eng._aot = aot_lib.ExecutableCache(str(tmp_path))
    eng.warmup(calibration[:4])
    sidecars = [f for f in os.listdir(str(tmp_path))
                if f.endswith(".cost.json")]
    if not sidecars:
        pytest.skip("backend exposes no cost analysis")
    key = sidecars[0][: -len(".cost.json")]
    cost = costmodel.load_program_cost(key, str(tmp_path))
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    # Synthetic device peaks: the classification machinery, not the HW.
    rl = costmodel.roofline(
        cost, peak_flops=1e12, hbm_bytes_per_s=1e11
    )
    assert rl["bound"] in ("compute", "memory")


# --------------------------------------------------------------------------
# promotion: hot swap f32 -> int8 under live traffic
# --------------------------------------------------------------------------


def test_hot_swap_f32_to_int8_mid_traffic_zero_drops(
    f32_bundle_dir, int8_bundle_dir, calibration
):
    """The audited promotion: a live f32 ReplicaSet swaps to the int8
    bundle while requests are in flight — every request answers (zero
    drops), traffic compiles nothing (the swap warmed the int8 programs
    off-path), and post-swap answers are the int8 model's."""
    bundle_a = serve.load_bundle(f32_bundle_dir)
    bundle_b = serve.load_bundle(int8_bundle_dir)
    x = np.asarray(calibration[:3], np.float32)
    expected_b = serve.InferenceEngine(
        bundle_b, max_bucket=8, persistent_cache=False
    ).predict(x)

    rs = serve.ReplicaSet(bundle_a, num_replicas=2, restart=False,
                          max_bucket=8)
    errors, answered = [], [0]
    stop = threading.Event()

    def traffic():
        while not stop.is_set():
            try:
                out = rs.predict(x)
                assert out.shape[0] == 3
                answered[0] += 1
            except Exception as exc:  # noqa: BLE001 - any drop fails below
                errors.append(exc)
                return

    try:
        rs.warmup(x)
        threads = [threading.Thread(target=traffic) for _ in range(3)]
        for t in threads:
            t.start()
        event = rs.hot_swap(bundle_b)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert answered[0] > 0
        assert event["replicas_swapped"] == 2
        assert rs.bundle.precision == "int8"
        # Post-swap traffic answers the int8 model, bit-for-bit.
        for _ in range(4):
            np.testing.assert_array_equal(rs.predict(x), expected_b)
        # The acceptance counter: the swap warmed off-path; nothing the
        # traffic did (f32 before, int8 after) compiled a program.
        assert rs.program_stats()["new_programs_since_warmup"] == 0
        for per in rs.program_stats()["per_replica"]:
            assert per["precision"] == "int8"
    finally:
        stop.set()
        rs.close()


def test_server_metrics_report_precision_and_delta(int8_bundle_dir):
    bundle = serve.load_bundle(int8_bundle_dir)
    srv = serve.PredictionServer(bundle, port=0, num_replicas=1,
                                 max_bucket=8)
    try:
        assert srv.handle_healthz()["precision"] == "int8"
        m = srv.handle_metrics()
        assert m["precision"] == "int8"
        assert m["quality_delta_mape"] == bundle.quality_delta_mape
    finally:
        srv.close()


# --------------------------------------------------------------------------
# PBT: quality_after_quant objective
# --------------------------------------------------------------------------


def test_pbt_quality_after_quant_selects_on_int8_mape(tmp_path):
    """The quant-aware objective: the vectorized driver fake-quantizes
    every surviving row at sweep end and emits its int8 validation MAPE
    as a final ``pbt_objective`` record — selection then prefers the
    model that survives int8."""
    from distributed_machine_learning_tpu.data import Dataset
    from distributed_machine_learning_tpu.tune.trial import TrialStatus
    from distributed_machine_learning_tpu.tune.vectorized import (
        run_vectorized,
    )

    rng = np.random.default_rng(7)
    x = rng.normal(size=(96, 8, 4)).astype(np.float32)
    w = rng.normal(size=(4,)).astype(np.float32)
    y = (x.mean(axis=1) @ w)[:, None].astype(np.float32)
    train, val = Dataset(x[:64], y[:64]), Dataset(x[64:], y[64:])

    pbt = tune.PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={
            "learning_rate": tune.loguniform(1e-3, 1e-1),
        },
        quantile_fraction=0.25,
        seed=3,
        objective="quality_after_quant",
    )
    assert pbt.quant_aware is True
    space = {
        "model": "mlp", "hidden_sizes": (16, 8),
        "learning_rate": tune.choice([3e-2, 1e-7]),
        "weight_decay": 1e-6, "seed": tune.randint(0, 10_000),
        "num_epochs": 4, "batch_size": 16,
        "loss_function": "mse", "lr_schedule": "constant",
    }
    analysis = run_vectorized(
        space, train_data=train, val_data=val,
        metric="validation_mse", mode="min", num_samples=4,
        scheduler=pbt, storage_path=str(tmp_path), seed=2, verbose=0,
    )
    assert all(t.status == TrialStatus.TERMINATED for t in analysis.trials)
    for t in analysis.trials:
        final = t.results[-1]
        assert final["quant_precision"] == "int8"
        assert final["pbt_objective"] == final["quant_mape"] >= 0
    # Selection over the emitted objective works through the standard
    # analysis machinery (what export_bundle would be handed).
    quant_analysis = tune.ExperimentAnalysis(
        analysis.trials, metric="pbt_objective", mode="min",
        root=analysis.root,
    )
    best = quant_analysis.best_trial
    assert best.results[-1]["quant_mape"] == min(
        t.results[-1]["quant_mape"] for t in analysis.trials
    )

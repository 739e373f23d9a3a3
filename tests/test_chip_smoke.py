"""chip_smoke.py's phases at toy size on the CPU (rehearsal 1), the
four-chip comparisons on four virtual devices (rehearsal 2), its refusal
to run without a TPU, and the compile-cache placement rule it relies on.

The script has no CPU mode and gains no switch for these tests: its phases
are functions that take their sizes.  Kernels a phase calls directly run in
the Pallas interpreter here; what only a TPU can satisfy (the Pallas call
inside the compiled epoch program) is asserted by ``main()`` on the chip and
pinned to its CPU value here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE_PY = os.path.join(REPO_ROOT, "chip_smoke.py")
spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE_PY)
cs = importlib.util.module_from_spec(spec)
sys.modules.setdefault("chip_smoke", cs)
spec.loader.exec_module(cs)

TINY_MODEL = dict(d_model=16, num_heads=2, num_layers=1, dim_feedforward=32)


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chip_smoke"))


@pytest.fixture(scope="module")
def four():
    devices = jax.devices()
    assert len(devices) >= 4, "conftest gives the CPU backend 8 devices"
    return list(devices[:4])


# -- one-chip phases ---------------------------------------------------------


@pytest.fixture(scope="module")
def train_result(storage):
    return cs.phase_train(
        d_model=32, num_heads=2, dim_feedforward=64, num_layers=2,
        seq_len=64, features=4, batch_size=4, n_train=12, n_val=4,
        num_epochs=2, storage=storage, interpret=True,
    )


@pytest.mark.parametrize("attention_type", ["flash", "scaled_dot_product"])
def test_train_phase_runs_both_attention_types(train_result, attention_type):
    r = train_result[attention_type]
    assert len(r["losses"]) == 2 and np.isfinite(r["losses"]).all()
    # Off the TPU the layer takes its lax.scan / XLA branch: no Mosaic
    # call — exactly what main() refuses to accept on the chip.
    assert r["pallas_calls"] == 0
    assert r["step_s"] > 0


def test_train_phase_checks_kernel_against_xla(train_result):
    errs = train_result["flash_vs_dense"]
    assert set(errs) == {"out", "dq", "dk", "dv"}
    assert max(errs.values()) <= cs.ATTENTION_TOL


def test_a_failed_check_raises():
    """No phase may end quietly: a comparison beyond tolerance raises."""
    with pytest.raises(AssertionError, match="beyond"):
        cs.flash_vs_dense(1, 32, 2, 16, interpret=True, tol=1e-9)


def test_sweep_phase(storage):
    out = cs.phase_sweep(
        **TINY_MODEL, seq_len=24, features=4, batch_size=16, num_trials=6,
        num_epochs=3, data_steps=3000, storage=storage,
    )
    assert out["compile"]["program_misses"] == 1
    with open(os.path.join(storage, "sweep", "experiment_state.json")) as f:
        assert len(json.load(f)["trials"]) == 6


@pytest.fixture(scope="module")
def cohort(storage):
    return cs.phase_cohort(
        jax.devices()[0], num_trials=4, num_epochs=3, seq_len=8, features=4,
        n_samples=128, storage=storage,
    )


def test_cohort_phase_runs_trials_concurrently_on_one_device(cohort):
    out, analysis, _ = cohort
    assert out["peak_concurrent"] > 1
    assert analysis.num_terminated() == 4
    assert {t.last_result is not None for t in analysis.trials} == {True}


def test_serve_phase_answers_over_http(cohort, storage):
    _, analysis, val = cohort
    out = cs.phase_serve(
        analysis, val, batch_sizes=(1, 4, 8), requests_per_size=3,
        storage=storage,
    )
    assert out["requests"] == 9
    assert out["new_programs_since_warmup"] == 0
    # XLA:CPU may refuse a program imported from a warm AOT directory (it
    # then recompiles), so only the TPU run's zero is meaningful.
    assert {"aot_exports", "aot_imports", "aot_unsupported"} <= set(out)


# -- four-chip phases on four virtual devices --------------------------------


def test_sharded_trial_matches_one_device_and_spreads(four, storage):
    out = cs.phase_sharded_trial(
        four, d_model=16, num_heads=2, dim_feedforward=32, num_layers=1,
        seq_len=16, features=4, batch_size=16, n_samples=128, num_epochs=2,
        storage=storage,
    )
    assert out["shard_device_ids"] == sorted(d.id for d in four)
    assert "tp" in out["example"][1]


def test_sharded_sweep_matches_one_device_and_spreads(four, storage):
    out = cs.phase_sharded_sweep(
        four, **TINY_MODEL, seq_len=24, features=4, batch_size=16,
        num_trials=8, num_epochs=3, data_steps=3000, storage=storage,
    )
    assert out["population_sharded_over"] == 4
    assert out["shard_device_ids"] == sorted(d.id for d in four)


@pytest.mark.parametrize("use_flash,flash_inner", [(True, True),
                                                   ("auto", False)])
def test_ring_phase_matches_dense(four, use_flash, flash_inner):
    """Forced flash inner kernels (interpreted) and the dense inner path
    that 'auto' resolves to off the TPU both match one-device attention."""
    out = cs.phase_ring(
        four, batch=1, seq_len=64, heads=2, head_dim=16,
        use_flash=use_flash, interpret=True,
    )
    assert out["flash_inner"] is flash_inner and out["sp"] == 4


def test_sharded_live_arrays_sees_only_split_arrays(four):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(four), ("x",))
    split = jax.device_put(np.zeros((8, 3)), NamedSharding(mesh, P("x")))
    whole = jax.device_put(np.zeros((5, 7)), NamedSharding(mesh, P()))
    seen = cs.sharded_live_arrays(4)
    assert ((8, 3), str(P("x")), sorted(d.id for d in four)) in seen
    assert all(shape != (5, 7) for shape, _, _ in seen)
    del split, whole


# -- the script as a whole ---------------------------------------------------


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_cpu(argv, capsys):
    assert cs.main(argv) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "no TPU" in captured.err


def test_script_alone_fails_without_the_package(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    it exits non-zero and prints no result."""
    shutil.copy(_SMOKE_PY, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_phase_limit_ends_the_process(tmp_path):
    code = (
        "import importlib.util, sys, time\n"
        f"s = importlib.util.spec_from_file_location('cs', {_SMOKE_PY!r})\n"
        "cs = importlib.util.module_from_spec(s); s.loader.exec_module(cs)\n"
        "with cs.phase('stuck', 0.2):\n"
        "    time.sleep(30)\n"
        "print('survived')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
    )
    assert proc.returncode == 2
    assert "survived" not in proc.stdout
    assert "passed its" in proc.stderr


def test_no_switch_and_no_swallowed_failure():
    """The script keeps its contract by construction: one option
    (--chips), no environment switch, no try/except around a phase."""
    with open(_SMOKE_PY) as f:
        src = f.read()
    assert "except" not in src.replace("except importlib.metadata", "")
    assert src.count("add_argument(") == 1
    assert "os.environ[" not in src and "setdefault(" not in src


# -- the compile-cache rule --------------------------------------------------


@pytest.fixture
def fresh_cache_state(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from distributed_machine_learning_tpu.compilecache import tracker

    was_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(tracker, "_enabled_dir", None)
    yield tracker
    jax.config.update("jax_compilation_cache_dir", was_dir)
    compilation_cache.reset_cache()


def _spy_config_updates(monkeypatch):
    seen = []
    real = jax.config.update

    def update(name, value):
        seen.append(name)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return seen


def test_cache_dir_from_jax_variable_sets_nothing_in_code(
        fresh_cache_state, monkeypatch, tmp_path):
    tracker = fresh_cache_state
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    seen = _spy_config_updates(monkeypatch)
    # Neither an argument nor the retired package variables outrank it.
    monkeypatch.setenv("DML_TPU_COMPILE_CACHE", str(tmp_path / "old"))
    monkeypatch.setenv("DML_TPU_AOT_CACHE", str(tmp_path / "old_aot"))
    got = tracker.enable_persistent_cache(str(tmp_path / "arg"))
    assert got == str(tmp_path / "ext")
    assert "jax_compilation_cache_dir" not in seen
    assert "jax_persistent_cache_min_compile_time_secs" in seen

    from distributed_machine_learning_tpu.compilecache import aot

    assert aot.default_aot_dir() == str(tmp_path / "ext" / "aot")


def test_cache_dir_default_is_fixed_inside_the_checkout(
        fresh_cache_state, monkeypatch):
    tracker = fresh_cache_state
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = _spy_config_updates(monkeypatch)
    want = os.path.join(REPO_ROOT, ".dml_cache", "xla")
    assert tracker.enable_persistent_cache() == want
    assert "jax_compilation_cache_dir" in seen
    assert jax.config.jax_compilation_cache_dir == want

    from distributed_machine_learning_tpu.compilecache import aot

    assert aot.default_aot_dir() == os.path.join(want, "aot")


def test_cache_root_is_git_ignored_and_holds_the_native_build():
    from distributed_machine_learning_tpu.compilecache.tracker import (
        cache_root,
    )
    from distributed_machine_learning_tpu.data import native

    assert cache_root() == os.path.join(REPO_ROOT, ".dml_cache")
    assert native._CACHE_DIR == os.path.join(cache_root(), "native")
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".dml_cache/" in f.read().split()


def test_argument_places_the_cache_only_when_the_variable_is_unset(
        fresh_cache_state, monkeypatch, tmp_path):
    tracker = fresh_cache_state
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert tracker.resolve_cache_dir(str(tmp_path)) == str(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert tracker.resolve_cache_dir(str(tmp_path)) == "/elsewhere"

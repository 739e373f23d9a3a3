"""What decides ``correct``, at sizes a test run can hold.

* A sound run of each driver kind comes out correct.
* The control comes out not correct: the plain reference put in the
  program's place and computed in the precision below the one the tiny
  configuration states (float32 here, so bfloat16 operands; the real
  cells' controls, fp8 operands under bfloat16, were read on the chip and
  are in PERF.md).
* The rest of a run, driven with the timed path broken underneath, comes
  out not correct, once for each fault the cell can have: a step that
  returns its state unchanged; half of the batch left out and the mean
  taken over the rest; an answer altered where it is produced.
"""

import tempfile

import pytest

import tiny
from benchmark.drivers import sweep as sweep_driver
from benchmark.drivers import train as train_driver
from benchmark.reference import regressor as ref
from benchmark.run import Run


@pytest.fixture(autouse=True)
def fresh_programs():
    """The program caches its traced programs by configuration and data: a
    test that breaks the timed path must not hand its programs on."""
    from distributed_machine_learning_tpu import tune

    tune.clear_program_cache()
    yield
    tune.clear_program_cache()


def _run(cell, seed=3):
    import jax

    return Run(cell=cell, seed=seed, seconds=0.5, traced=False,
               devices=jax.devices()[:1], work_dir=tempfile.mkdtemp(),
               peaks=None)


def failed(result):
    return sorted(
        name for name, c in result["checks"].items()
        if not c["value"] <= c["limit"]
    )


# -- sound runs --------------------------------------------------------------


def test_train_sound_run_is_correct(tmp_path):
    result = tiny.drive(tiny.train_cell(), tmp_path)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def test_sweep_sound_run_is_correct(tmp_path):
    result = tiny.drive(tiny.sweep_cell(), tmp_path)
    assert result["correct"], result["checks"]
    assert result["metrics"]["trial_done_p95_ms"]["value"] > 0


# -- the controls ------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_is_not_correct(seed):
    cell = tiny.train_cell()
    run = _run(cell, seed)
    cfg = train_driver.trial_config(run)
    train, val = train_driver.make_data(run)
    want = train_driver.reference_epoch(run, cfg, train, val)
    control = train_driver.reference_epoch(run, cfg, train, val, quant=ref.bf16)
    checks = train_driver.compare(control, want, cell.traffic["limits"])
    assert not all(ok for *_, ok in checks), checks


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sweep_control_is_not_correct(seed):
    cell = tiny.sweep_cell()
    run = _run(cell, seed)
    state = sweep_driver.setup(run)
    confs = [{"learning_rate": 3e-3, "weight_decay": 1e-4, "seed": s}
             for s in (5, 6, 7, 8)]
    want = sweep_driver.reference_curves(run, state, confs, [4] * 4)
    control = sweep_driver.reference_curves(
        run, state, confs, [4] * 4, quant=ref.bf16
    )
    gap = sweep_driver.curve_gaps(control, want, 4)["first_epoch_gap_med"]
    assert gap > cell.traffic["limits"]["first_epoch_gap_med"], gap


@pytest.mark.parametrize("cell", [tiny.train_cell(), tiny.sweep_cell()],
                         ids=lambda c: c.name)
def test_limit_readings_tell_sound_from_planted(cell):
    """The tool the limits are read with (``benchmark/controls.py``), at a
    tiny size: a sound run stays under every limit; the control, half a
    batch left out and a state left unchanged each pass one."""
    import jax

    from benchmark import controls

    limits = cell.traffic["limits"]
    over = {}
    for line in controls.read(cell, [5, 6], 1, jax.devices()[:1], group=2,
                              broken=1):
        over[(line["seed"], line["what"])] = sorted(
            n for n in limits if n in line and not line[n] <= limits[n]
        )
    assert over.pop((5, "sound")) == [] and over.pop((6, "sound")) == [], over
    assert over and all(over.values()), over
    whats = {what for _, what in over}
    assert whats >= {"control", "half_batch"}
    assert ("half_batch_program" in whats) == (cell.traffic["driver"] == "train")


# -- the timed path broken underneath -----------------------------------------


def _frozen_epoch(make_epoch_fn):
    def broken(*args, **kwargs):
        epoch = make_epoch_fn(*args, **kwargs)

        def unchanged(params, opt_state, batch_stats, x, y, key):
            _, _, _, loss = epoch(params, opt_state, batch_stats, x, y, key)
            return params, opt_state, batch_stats, loss

        return unchanged

    return broken


def _half_batch_loss(get_loss):
    def broken(name):
        loss = get_loss(name)
        return lambda preds, y: loss(
            preds[: preds.shape[0] // 2], y[: y.shape[0] // 2]
        )

    return broken


def test_train_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    from distributed_machine_learning_tpu.tune import trainable

    monkeypatch.setattr(
        trainable, "make_epoch_fn", _frozen_epoch(trainable.make_epoch_fn)
    )
    result = tiny.drive(tiny.train_cell(), tmp_path)
    assert not result["correct"]
    assert "param_change_gap_med" in failed(result)


def test_train_half_batch_is_not_correct(tmp_path, monkeypatch):
    from distributed_machine_learning_tpu.tune import trainable

    monkeypatch.setattr(
        trainable, "get_loss", _half_batch_loss(trainable.get_loss)
    )
    result = tiny.drive(tiny.train_cell(), tmp_path)
    assert not result["correct"]
    assert "param_change_half_ratio_med" in failed(result)


def test_sweep_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    from distributed_machine_learning_tpu.tune import vectorized

    monkeypatch.setattr(
        vectorized, "make_epoch_fn", _frozen_epoch(vectorized.make_epoch_fn)
    )
    result = tiny.drive(tiny.sweep_cell(), tmp_path)
    assert not result["correct"]
    assert "first_epoch_gap_med" in failed(result)


def test_sweep_half_batch_is_not_correct(tmp_path, monkeypatch):
    from distributed_machine_learning_tpu.tune import vectorized

    monkeypatch.setattr(
        vectorized, "get_loss", _half_batch_loss(vectorized.get_loss)
    )
    result = tiny.drive(tiny.sweep_cell(), tmp_path)
    assert not result["correct"]
    assert "first_epoch_train_gap_med" in failed(result)


def test_sweep_altered_answer_is_not_correct(tmp_path, monkeypatch):
    """Every reported validation loss raised by a twentieth where the
    population's results are emitted."""
    from distributed_machine_learning_tpu.tune import vectorized

    emit = vectorized._emit_epoch_records

    def altered(batch, rows, active, lrs, epoch, step_count, shape_val, now,
                train_losses, metrics_np, *args, **kwargs):
        metrics_np = dict(metrics_np)
        metrics_np["validation_loss"] = metrics_np["validation_loss"] * 1.05
        return emit(batch, rows, active, lrs, epoch, step_count, shape_val,
                    now, train_losses, metrics_np, *args, **kwargs)

    monkeypatch.setattr(vectorized, "_emit_epoch_records", altered)
    result = tiny.drive(tiny.sweep_cell(), tmp_path)
    assert not result["correct"]
    assert "first_epoch_gap_med" in failed(result)


def test_sweep_wrong_stop_is_not_correct(tmp_path, monkeypatch):
    """A scheduler that lets every trial run on is seen by the replay."""
    from distributed_machine_learning_tpu.tune.schedulers import asha

    monkeypatch.setattr(
        asha.ASHAScheduler, "on_trial_result",
        lambda self, trial, result: (
            asha.STOP if result["training_iteration"] >= self.max_t
            else asha.CONTINUE
        ),
    )
    result = tiny.drive(tiny.sweep_cell(), tmp_path)
    assert not result["correct"]
    assert "asha_ends_moved" in failed(result)


def test_plain_asha_rule():
    from benchmark.reference import asha

    assert asha.rungs(8, 1, 2) == [1, 2, 4, 8]
    # Four trials, eta 2: after epoch 1 the second of two recorded is cut if
    # worse than the best; the first always goes on.
    curves = [[1.0] * 8, [2.0] * 8, [0.5] * 8, [3.0] * 8]
    assert asha.replay(curves, max_t=8, grace=1, eta=2) == [8, 1, 8, 1]

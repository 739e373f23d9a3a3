"""Driver kind ``train``: one trial through ``tune.run(train_regressor)``.

Set-up makes the data from the seed and runs ``tune.run`` for one epoch,
which compiles (or reads from the cache) the cell's epoch and evaluation
programs.  The window is the same call on the same configuration, data and
device, so it drives the same compiled programs (the program caches them
by exactly these), for ``--seconds``, through every report, evaluation and
checkpoint a user pays.  Both calls start the trial from the seed, so the
window's first epoch is the epoch that set-up ran.

``check`` replays that first epoch with the plain reference, from the seed
alone, once the window has closed, and compares what the window itself
reported and saved for its first epoch: the training and validation loss
and the checkpoint (Adam's first moment, which is the gradients as the
optimizer got them, and the change of every parameter), leaf by leaf, by
the gap of the norms and by the norm of the difference.  Set-up's epoch
serves only ``loss_rerun_gap``: the window's first loss has to equal it.
Which of these numbers are compared is the traffic file's ``limits``: the
moment's are read and printed only, since eight steps' gradients cancel in
it on some seeds and every perturbation then reads alike (PERF.md,
section 2).
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from benchmark import datagen
from benchmark.run import judge


@dataclass
class State:
    config: dict
    train: Any
    val: Any
    warm_record: dict
    warm_checkpoint: str
    window_records: List[dict] = None
    window_checkpoint: str = ""


def trial_config(run) -> dict:
    t = run.cell.traffic
    cfg = dict(run.cell.config["trial"])
    cfg.update(
        batch_size=int(t["batch_size"]),
        max_seq_length=int(t["seq_len"]),
        num_epochs=int(t["num_epochs"]),
        seed=int(run.seed),
    )
    return cfg


def make_data(run, seed=None):
    from distributed_machine_learning_tpu.data.loader import Dataset

    t = run.cell.traffic
    xt, yt, xv, yv = datagen.make_windows(
        run.seed if seed is None else seed,
        n_train=int(t["batch_size"]) * int(t["steps_per_epoch"]),
        n_val=int(t["val_windows"]),
        seq_len=int(t["seq_len"]),
        features=int(run.cell.config["features"]),
    )
    return Dataset(xt, yt), Dataset(xv, yv)


def _result_stamps(run):
    """A ``tune.Callback`` that stamps every result on the host's clock and
    notes where the trial saved its first checkpoint; the list it stamps
    into and the list of that one path."""
    from distributed_machine_learning_tpu import tune

    stamps, first_checkpoint = [], []

    class _Cb(tune.Callback):
        def on_trial_result(self, trial, result):
            with run.annotate("on_trial_result"):
                stamps.append(time.perf_counter())
                if not first_checkpoint:
                    first_checkpoint.append(trial.latest_checkpoint or "")

    return _Cb(), stamps, first_checkpoint


def _tune_run(run, cfg, train, val, name, **kwargs):
    from distributed_machine_learning_tpu import tune

    return tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        cfg, metric="validation_loss", mode="min", num_samples=1,
        devices=[run.devices[0]], storage_path=run.work_dir, name=name,
        verbose=0, **kwargs,
    )


def setup(run, data=None) -> State:
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    cfg = trial_config(run)
    train, val = data or make_data(run)
    with run.annotate("warmup"):
        analysis = _tune_run(
            run, cfg, train, val, "warmup", stop={"training_iteration": 1}
        )
    trial = analysis.trials[0]
    if trial.status != TrialStatus.TERMINATED or len(trial.results) != 1:
        raise SystemExit(
            f"warm-up trial did not finish its epoch: {trial.status} "
            f"{trial.error}"
        )
    if not trial.latest_checkpoint:
        raise SystemExit("warm-up trial wrote no checkpoint")
    return State(
        config=cfg, train=train, val=val,
        warm_record=dict(trial.results[0]),
        warm_checkpoint=trial.latest_checkpoint,
    )


def window(run, state: State) -> None:
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    t = run.cell.traffic
    callback, stamps, first_checkpoint = _result_stamps(run)
    t0 = time.perf_counter()
    analysis = _tune_run(
        run, state.config, state.train, state.val, "window",
        time_budget_s=run.seconds, callbacks=[callback],
    )
    t1 = time.perf_counter()
    trial = analysis.trials[0]
    state.window_records = [dict(r) for r in trial.results]
    state.window_checkpoint = first_checkpoint[0] if first_checkpoint else ""
    epochs = len(trial.results)
    tokens_per_epoch = (
        int(t["batch_size"]) * int(t["steps_per_epoch"]) * int(t["seq_len"])
    )
    run.window_s = t1 - t0
    run.attempted = max(epochs, 1)
    run.failed = 0 if trial.status == TrialStatus.TERMINATED else 1
    run.metrics["train_tokens_per_s"] = epochs * tokens_per_epoch / (t1 - t0)
    marks = [t0] + stamps
    run.spans["epoch"] = [b - a for a, b in zip(marks, marks[1:])]
    from benchmark import flops

    run.facts.update(
        epochs=epochs,
        tokens=epochs * tokens_per_epoch,
        train_flops=epochs * int(t["batch_size"]) * int(t["steps_per_epoch"])
        * flops.train_flops_per_window(
            state.config, int(t["seq_len"]), int(run.cell.config["features"])
        ),
        attention_call=dict(
            batch=int(t["batch_size"]), seq_len=int(t["seq_len"]),
            heads=int(state.config["num_heads"]),
            head_dim=int(state.config["d_model"])
            // int(state.config["num_heads"]),
        ),
    )
    print(f"[bench] window: {epochs} epochs, {epochs * tokens_per_epoch} "
          f"tokens in {t1 - t0:.3f}s; trial {trial.status.value}", flush=True)


def release(run, state: State) -> None:
    from distributed_machine_learning_tpu import tune

    tune.clear_program_cache()


# ---------------------------------------------------------------------------
# The output check


def _find(tree, key):
    """The first sub-tree stored under ``key`` anywhere in a nested dict."""
    if isinstance(tree, dict):
        if key in tree:
            return tree[key]
        for v in tree.values():
            found = _find(v, key)
            if found is not None:
                return found
    return None


def _leaves(tree) -> Dict[str, np.ndarray]:
    import jax

    return {
        jax.tree_util.keystr(path): np.asarray(leaf, np.float32)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def leaf_rows(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]):
    """For every leaf (the reference's norm, the program's norm, the norm
    of their difference)."""
    rows = {}
    for k, w in want.items():
        w = np.asarray(w, np.float64)
        g = np.asarray(got[k], np.float64).reshape(w.shape)
        rows[k] = (float(np.linalg.norm(w)), float(np.linalg.norm(g)),
                   float(np.linalg.norm(g - w)))
    return rows


def leaf_gaps(rows, keep, what: str = "") -> Dict[str, float]:
    """Leaf by leaf the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: the median, the ninth decile and the worst of
    them, and the three worst leaves on an earlier line."""
    median = float(np.median([rows[k][0] for k in keep]))
    gaps = sorted(
        ((abs(rows[k][1] - rows[k][0]) / max(rows[k][0], median), k)
         for k in keep),
        reverse=True,
    )
    if what:
        for gap, k in gaps[:3]:
            print(f"[bench] check {what}_gap: {gap:.4g} at {k}: program "
                  f"{rows[k][1]:.6g}, reference {rows[k][0]:.6g} (median leaf "
                  f"{median:.6g})", flush=True)
    values = np.asarray([g for g, _ in gaps])
    # The norm of the difference, printed beside the gap of the norms: it
    # bounds it from above and is what ``half_ratios`` weighs.
    diffs = [rows[k][2] / max(rows[k][0], median) for k in keep]
    return {"gap_med": float(np.median(values)),
            "gap_p90": float(np.quantile(values, 0.9)),
            "gap_max": float(values.max()),
            "diff_med": float(np.median(diffs))}


def half_ratios(rows, rows_half, keep) -> Dict[str, float]:
    """Leaf by leaf, how far the program's vector lies from the reference's
    over how far it lies from that of the reference with half of every
    batch left out: under 1 where it is nearer to the whole batch's.  The
    program's own rounding moves a leaf about as far as half a batch does
    (PERF.md, section 2), on some seeds farther than on others, so no
    distance to the reference alone tells the two apart; which of the two
    references is nearer does, seed by seed."""
    values = np.asarray([
        rows[k][2] / max(rows_half[k][2], 1e-30) for k in keep
    ])
    return {"half_ratio_med": float(np.median(values)),
            "half_ratio_p90": float(np.quantile(values, 0.9))}


_STEPS: Dict[tuple, Any] = {}


def _reference_programs(run, cfg, quant, rows_used):
    """The reference's step and evaluation for this cell, traced once a
    process whatever the seed."""
    from benchmark.reference import regressor as ref

    t = run.cell.traffic
    key = (
        json.dumps({k: v for k, v in cfg.items() if k != "seed"}, sort_keys=True),
        int(t["batch_size"]), int(t["reference_block_rows"]),
        int(t["num_epochs"]) * int(t["steps_per_epoch"]),
        getattr(quant, "__name__", None), rows_used,
    )
    if key not in _STEPS:
        kwargs = {} if quant is None else {"quant": quant}
        _STEPS[key] = (
            ref.make_step(
                cfg, int(t["batch_size"]), int(t["reference_block_rows"]),
                int(t["num_epochs"]) * int(t["steps_per_epoch"]),
                rows_used=rows_used, **kwargs,
            ),
            ref.make_eval(cfg, int(t["reference_block_rows"]), **kwargs),
        )
    return _STEPS[key]


def reference_epoch(run, cfg: dict, train, val, *, quant=None,
                    rows_used=None, skip_update=False):
    """The first epoch from the seed by the plain reference: losses, Adam's
    first moment and the parameters' change leaf by leaf, validation loss,
    and the parameters it started from."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import regressor as ref

    t = run.cell.traffic
    batch = int(t["batch_size"])
    steps = int(t["steps_per_epoch"])
    rng_impl = ref.program_rng_impl(cfg)
    seed = int(cfg["seed"])
    params0 = ref.init_params(
        cfg, jax.random.key(ref.program_seed(seed, "init")),
        int(run.cell.config["features"]),
    )
    step, evaluate = _reference_programs(run, cfg, quant, rows_used)
    epoch_key = jax.random.key(
        ref.program_seed(seed, "epoch", 0), impl=rng_impl
    )
    x = jnp.asarray(train.x, jnp.float32)
    y = jnp.asarray(train.y, jnp.float32)
    t0 = time.time()
    params, opt, losses = ref.run_epoch(
        step, params0, ref.adam_init(params0), x, y, epoch_key,
        n_train=len(train), num_batches=steps, batch_size=batch,
        lr=float(cfg["learning_rate"]), wd=float(cfg.get("weight_decay", 0.0)),
    )
    losses.block_until_ready()
    print(f"[bench] reference: {steps} steps in {time.time() - t0:.1f}s, "
          f"losses {[round(float(l), 5) for l in losses]}", flush=True)
    if skip_update:
        params = params0
    val_loss = evaluate(
        params, jnp.asarray(val.x, jnp.float32), jnp.asarray(val.y, jnp.float32)
    )
    return {
        "train_loss": float(losses.mean()),
        "val_loss": float(val_loss),
        "mu": _leaves(opt["mu"]),
        "dparam": _leaves(jax.tree.map(lambda a, b: a - b, params, params0)),
        "params0": _leaves(params0),
    }


def numbers(got: dict, want: dict, half: dict = None) -> Dict[str, float]:
    """Every number the check reads, compared or not; ``half`` is the
    reference with half of every batch left out."""
    out = {
        "loss_e0_gap": abs(got["train_loss"] - want["train_loss"])
        / abs(want["train_loss"]),
        "val_e0_gap": abs(got["val_loss"] - want["val_loss"])
        / abs(want["val_loss"]),
    }
    moment = leaf_rows(got["mu"], want["mu"])
    # Leaves whose gradient is nought to rounding in the reference (a key's
    # bias under softmax) move under Adam by round-off alone: out by a rule
    # on the reference's own gradient, not by name.
    median = float(np.median([r[0] for r in moment.values()]))
    keep = [k for k, r in moment.items() if r[0] >= 1e-3 * median]
    change = leaf_rows(got["dparam"], want["dparam"])
    for name, key, rows in (("grad_moment", "mu", moment),
                            ("param_change", "dparam", change)):
        stats = leaf_gaps(rows, keep, name)
        if half is not None:
            stats.update(half_ratios(rows, leaf_rows(got[key], half[key]), keep))
        out.update({f"{name}_{stat}": v for stat, v in stats.items()})
    if "rerun_loss" in got:
        out["loss_rerun_gap"] = abs(
            got["rerun_loss"] - got["train_loss"]
        ) / abs(got["train_loss"])
    return out


def compare(got: dict, want: dict, limits: dict, half: dict = None):
    """(name, value, limit, ok) for every number compared."""
    return judge(numbers(got, want, half), limits)


def checkpoint_side(path: str, record: dict, params0) -> dict:
    """What an epoch of the program reported and saved, in the reference's
    terms: its losses, Adam's first moment and the parameters' change."""
    from distributed_machine_learning_tpu.tune.checkpoint import load_checkpoint

    ckpt = load_checkpoint(path)
    return {
        "train_loss": float(record.get("train_loss", float("nan"))),
        "val_loss": float(record.get("validation_loss", float("nan"))),
        "mu": _leaves(_find(ckpt["opt_state"], "mu")),
        "dparam": {
            k: v - params0[k].reshape(v.shape)
            for k, v in _leaves(ckpt["params"]).items()
        },
    }


def program_side(state: State, params0) -> dict:
    """What the window reported and saved for its first epoch."""
    if not state.window_records or not state.window_checkpoint:
        raise SystemExit("the window's first epoch saved no checkpoint")
    got = checkpoint_side(
        state.window_checkpoint, state.window_records[0], params0
    )
    got["rerun_loss"] = float(state.warm_record["train_loss"])
    return got


def _half_rows(run) -> int:
    return int(run.cell.traffic["batch_size"]) // 2


def check(run, state: State):
    want = reference_epoch(run, state.config, state.train, state.val)
    half = reference_epoch(run, state.config, state.train, state.val,
                           rows_used=_half_rows(run))
    got = program_side(state, want["params0"])
    return compare(got, want, run.cell.traffic["limits"], half)


def _broken_loss(get_loss):
    """The program's loss with half of every batch left out and the mean
    taken over the rest: the fault, planted in the program."""
    return lambda name: lambda preds, y: get_loss(name)(
        preds[: preds.shape[0] // 2], y[: y.shape[0] // 2]
    )


def readings(make_run, seeds, planted: int, control, group: int = 1,
             broken: int = 0):
    """What the limits are set from, read in one process: for every seed
    the numbers of a sound epoch of the program; for ``planted`` of the
    seeds, spread over them, those of the reference put in the program's
    place in the control's precision and with half of every batch left out
    (a state left unchanged reads 1 by the measure and needs no run); and
    for the first ``broken`` seeds (of those on the first data) those of
    the program itself with half of every batch left out of its loss.
    Yields (seed, what, numbers).

    The program traces its epoch anew for new data (most of a run's
    set-up), so each ``group`` seeds in a row train on the data of the
    first of them: their weights, batch orders and dropout differ.
    """
    import shutil

    import jax

    from benchmark.reference import regressor as ref
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import trainable

    data_seed = {s: seeds[i - i % group] for i, s in enumerate(seeds)}
    every = max(len(seeds) // max(planted, 1), 1)
    planted_at = set(list(range(0, len(seeds), every))[:planted])
    broken_at = [s for s in seeds[:broken] if data_seed[s] == seeds[0]]

    def program_epoch(seed, data):
        run = make_run(seed)
        state = setup(run, data)
        params0 = _leaves(ref.init_params(
            state.config,
            jax.random.key(ref.program_seed(int(state.config["seed"]), "init")),
            int(run.cell.config["features"]),
        ))
        side = checkpoint_side(state.warm_checkpoint, state.warm_record, params0)
        shutil.rmtree(run.work_dir, ignore_errors=True)
        return side

    # The program first, every seed, while nothing else is on the chip.
    sound, faulty, data = {}, {}, (None, None)
    for seed in seeds:
        if data[0] != data_seed[seed]:
            data = (data_seed[seed], make_data(make_run(seed), data_seed[seed]))
        sound[seed] = program_epoch(seed, data[1])
        print(f"[bench] seed {seed} on the data of {data_seed[seed]}: the "
              f"program's epoch read", flush=True)
    if broken_at:
        get_loss = trainable.get_loss
        trainable.get_loss = _broken_loss(get_loss)
        tune.clear_program_cache()
        try:
            data = make_data(make_run(seeds[0]), seeds[0])
            for seed in broken_at:
                faulty[seed] = program_epoch(seed, data)
                print(f"[bench] seed {seed}: the program's epoch with half of "
                      f"every batch left out read", flush=True)
        finally:
            trainable.get_loss = get_loss
    del data
    tune.clear_program_cache()
    for i, seed in enumerate(seeds):
        run = make_run(seed)
        cfg = trial_config(run)
        train, val = make_data(run, data_seed[seed])
        want = reference_epoch(run, cfg, train, val)
        half = reference_epoch(run, cfg, train, val, rows_used=_half_rows(run))
        sides = [("sound", sound.pop(seed))]
        if seed in faulty:
            sides.append(("half_batch_program", faulty.pop(seed)))
        if i in planted_at:
            sides += [
                ("control", reference_epoch(run, cfg, train, val, quant=control)),
                ("half_batch", half),
            ]
        for what, got in sides:
            yield seed, what, numbers(got, want, half)

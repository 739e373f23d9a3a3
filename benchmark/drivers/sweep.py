"""Driver kind ``sweep``: whole ASHA sweeps through ``tune.run_vectorized``.

Set-up makes the data from the seed and runs the traffic file's
``warmup_sweeps`` (one where the population keeps its size), which compile
(or read from the cache) the population programs the window will ask for.
The window runs whole sweeps back to back, each with a fresh searcher seed,
until ``--seconds`` have passed; a sweep that started before the deadline
runs to its end and is counted, with its time.

``check`` looks at what the window's sweeps reported, once the window has
closed.  For one sweep drawn from the seed: every trial ended; plain ASHA,
replayed on the reported losses, ends every trial where the program did;
the best trial is the one the program names; and for a sample of its trials
(the best one, ones that ran to the end, others drawn from the seed) the
plain reference trains the trial from its sampled learning rate, weight
decay and seed and gives the same training and validation loss after the
first epoch (the later epochs' are printed).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import numpy as np

from benchmark import datagen, flops
from benchmark.run import compile_events, judge


@dataclass
class SweepRecord:
    trial_ids: List[str]
    configs: List[dict]
    curves: List[List[float]]
    train_first: List[float]
    terminated: List[bool]
    best_trial_id: str


@dataclass
class State:
    space: dict
    train: Any
    val: Any
    sweeps: List[SweepRecord] = field(default_factory=list)


def _space(run) -> dict:
    from distributed_machine_learning_tpu import tune

    t = run.cell.traffic
    kinds = {"loguniform": tune.loguniform, "randint": tune.randint,
             "uniform": tune.uniform, "choice": tune.choice}
    space = dict(run.cell.config["trial"])
    for key, (kind, *args) in run.cell.config["search"].items():
        space[key] = kinds[kind](*args)
    space.update(
        batch_size=int(t["batch_size"]), max_seq_length=int(t["seq_len"]),
        num_epochs=int(t["max_t"]),
    )
    return space


def _sweep_seed(run, index: int) -> int:
    return (int(run.seed) * 1_000_003 + index + 1) % (2 ** 31 - 1)


def _one_sweep(run, state: State, index: int, name: str, stamps=None):
    """One ``run_vectorized`` call; returns (record, seconds)."""
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    t = run.cell.traffic
    t0 = time.perf_counter()
    callbacks = None
    if stamps is not None:
        class _Done(tune.Callback):
            def on_trial_complete(self, trial):
                stamps.append(time.perf_counter() - t0)

        callbacks = [_Done()]
    where = (
        {"devices": list(run.devices)} if len(run.devices) > 1
        else {"device": run.devices[0]}
    )
    with run.annotate("sweep"):
        analysis = tune.run_vectorized(
            state.space, train_data=state.train, val_data=state.val,
            metric="validation_loss", mode="min",
            num_samples=int(t["population"]),
            max_batch_trials=int(t["population"]),
            scheduler=tune.ASHAScheduler(
                max_t=int(t["max_t"]), grace_period=int(t["grace_period"]),
                reduction_factor=float(t["reduction_factor"]),
            ),
            storage_path=run.work_dir, name=name,
            seed=_sweep_seed(run, index), verbose=0, callbacks=callbacks,
            compaction=t.get("compaction", "auto"),
            epochs_per_dispatch=t.get("epochs_per_dispatch", "auto"),
            **where,
        )
    seconds = time.perf_counter() - t0
    trials = list(analysis.trials)
    record = SweepRecord(
        trial_ids=[tr.trial_id for tr in trials],
        configs=[
            {k: tr.config[k] for k in ("learning_rate", "weight_decay", "seed")}
            for tr in trials
        ],
        curves=[
            [float(r["validation_loss"]) for r in tr.results] for tr in trials
        ],
        train_first=[
            float(tr.results[0]["train_loss"]) if tr.results else float("nan")
            for tr in trials
        ],
        terminated=[tr.status == TrialStatus.TERMINATED for tr in trials],
        best_trial_id=analysis.best_trial.trial_id,
    )
    return record, seconds


def make_data(run):
    from distributed_machine_learning_tpu.data.loader import Dataset

    t = run.cell.traffic
    xt, yt, xv, yv = datagen.make_windows(
        run.seed,
        n_train=int(t["batch_size"]) * int(t["steps_per_epoch"]),
        n_val=int(t["val_windows"]), seq_len=int(t["seq_len"]),
        features=int(run.cell.config["features"]),
    )
    return Dataset(xt, yt), Dataset(xv, yv)


def setup(run) -> State:
    t = run.cell.traffic
    train, val = make_data(run)
    state = State(space=_space(run), train=train, val=val)
    for attempt in range(int(t.get("warmup_sweeps", 1))):
        before = compile_events()
        with run.annotate("warmup"):
            record, seconds = _one_sweep(
                run, state, -1 - attempt, f"warmup_{attempt}"
            )
        if not all(record.terminated):
            raise SystemExit("warm-up sweep left trials unfinished")
        print(f"[bench] warm-up sweep {attempt}: {len(record.curves)} trials, "
              f"{sum(map(len, record.curves))} trial-epochs in {seconds:.1f}s, "
              f"{compile_events() - before:.0f} compile requests", flush=True)
    return state


def window(run, state: State) -> None:
    t = run.cell.traffic
    done_s: List[float] = []
    walls: List[float] = []
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    index = 0
    while time.perf_counter() < deadline:
        record, seconds = _one_sweep(
            run, state, index, f"sweep_{index:03d}", stamps=done_s
        )
        state.sweeps.append(record)
        walls.append(seconds)
        index += 1
    t1 = time.perf_counter()
    trials = sum(len(s.curves) for s in state.sweeps)
    ended = sum(sum(s.terminated) for s in state.sweeps)
    run.window_s = t1 - t0
    run.attempted = trials
    run.failed = trials - ended
    run.metrics["sweep_trials_per_s"] = ended / (t1 - t0)
    done_ms = np.sort(np.asarray(done_s)) * 1e3
    # The 95th percentile by rank: the smallest time that 95 % of the
    # trials had their verdict by.
    rank = max(int(np.ceil(0.95 * len(done_ms))) - 1, 0)
    run.metrics["trial_done_p95_ms"] = float(done_ms[rank])
    run.spans["sweep"] = walls
    trial_epochs = sum(sum(map(len, s.curves)) for s in state.sweeps)
    windows_per_epoch = int(t["batch_size"]) * int(t["steps_per_epoch"])
    run.facts.update(
        sweeps=len(state.sweeps), trials=trials, trial_epochs=trial_epochs,
        train_flops=trial_epochs * windows_per_epoch
        * flops.train_flops_per_window(
            run.cell.config["trial"], int(t["seq_len"]),
            int(run.cell.config["features"]),
        ),
    )
    print(f"[bench] window: {len(state.sweeps)} sweeps, {ended} of {trials} "
          f"trials ended, {len(done_ms)} verdicts timed, {trial_epochs} "
          f"trial-epochs reported in {t1 - t0:.3f}s; median verdict "
          f"{float(np.median(done_ms)):.1f} ms", flush=True)


def release(run, state: State) -> None:
    from distributed_machine_learning_tpu import tune

    tune.clear_program_cache()


# ---------------------------------------------------------------------------
# The output check


def reference_curves(run, state: State, configs: List[dict],
                     lengths: List[int], *, quant=None, rows_used=None,
                     skip_update=False):
    """By the plain reference, from each trial's sampled hyperparameters
    and seed: its validation loss after every epoch, and its first epoch's
    training loss."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import regressor as ref

    t = run.cell.traffic
    cfg = run.cell.config["trial"]
    batch, steps = int(t["batch_size"]), int(t["steps_per_epoch"])
    kwargs = {} if quant is None else {"quant": quant}
    step = ref.make_step(
        cfg, batch, batch, int(t["max_t"]) * steps, rows_used=rows_used,
        **kwargs,
    )
    evaluate = ref.make_eval(cfg, int(t["val_windows"]), **kwargs)
    rng_impl = ref.program_rng_impl(cfg)
    x = jnp.asarray(state.train.x, jnp.float32)
    y = jnp.asarray(state.train.y, jnp.float32)
    xv = jnp.asarray(state.val.x, jnp.float32)
    yv = jnp.asarray(state.val.y, jnp.float32)
    curves, train_first = [], []
    for conf, n_epochs in zip(configs, lengths):
        base = jax.random.key(np.uint32(int(conf["seed"])), impl=rng_impl)
        params = ref.init_params(
            cfg, jax.random.split(base)[0], int(run.cell.config["features"])
        )
        opt = ref.adam_init(params)
        curve = []
        for epoch in range(n_epochs):
            new_params, opt, losses = ref.run_epoch(
                step, params, opt, x, y, jax.random.fold_in(base, epoch),
                n_train=len(state.train), num_batches=steps, batch_size=batch,
                lr=float(conf["learning_rate"]),
                wd=float(conf["weight_decay"]),
            )
            if epoch == 0:
                train_first.append(float(losses.mean()))
            if not skip_update:
                params = new_params
            curve.append(float(evaluate(params, xv, yv)))
        curves.append(curve)
    return curves, train_first


def pick(run, state: State):
    """The sweep and the trials that the check looks at, from the seed: the
    trials whose whole curves are followed (the best, two that ran to the
    end, others drawn), then those followed through their first epoch."""
    t = run.cell.traffic
    rng = np.random.default_rng([int(run.seed), 2718])
    sweep = state.sweeps[int(rng.integers(len(state.sweeps)))]
    n = len(sweep.curves)
    best = sweep.trial_ids.index(sweep.best_trial_id)
    full = [i for i in range(n) if len(sweep.curves[i]) >= int(t["max_t"])]
    chosen = [best]
    chosen += [int(i) for i in rng.permutation(full)[:2] if i not in chosen]
    order = [int(i) for i in rng.permutation(n) if int(i) not in chosen]
    used = max(int(t["check_trials"]) - len(chosen), 0)
    chosen += order[:used]
    first_only = order[used: used + int(t["check_first_epoch_trials"])]
    return sweep, chosen, first_only


def _med_max(name: str, values) -> Dict[str, float]:
    values = np.asarray(values, np.float64)
    return {f"{name}_med": float(np.median(values)),
            f"{name}_max": float(values.max())}


def curve_gaps(got, want, whole: int) -> Dict[str, float]:
    """Relative gaps between what the program reported and the reference,
    each side (validation curves, first training losses): over the first
    epoch of every trial looked at (which no later step has widened), of
    the validation loss and of the training loss (mean over the epoch's
    steps, each on the rows that step drew: half a batch cannot match it);
    over every epoch of the first ``whole`` trials, whose whole curves were
    followed, of the validation loss."""
    (got_val, got_train), (want_val, want_train) = got, want
    every = [
        abs(g - w) / abs(w)
        for gc, wc in zip(got_val[:whole], want_val[:whole])
        for g, w in zip(gc, wc)
    ]
    return {
        **_med_max("first_epoch_gap", [
            abs(gc[0] - wc[0]) / abs(wc[0]) for gc, wc in zip(got_val, want_val)
        ]),
        **_med_max("first_epoch_train_gap", [
            abs(g - w) / abs(w) for g, w in zip(got_train, want_train)
        ]),
        **_med_max("curve_gap", every),
    }


def decisions(run, sweep: SweepRecord):
    """(trials whose end plain ASHA puts elsewhere, 1 if the best trial is
    another than the program names)."""
    from benchmark.reference import asha

    t = run.cell.traffic
    lengths = asha.replay(
        sweep.curves, max_t=int(t["max_t"]), grace=int(t["grace_period"]),
        eta=float(t["reduction_factor"]),
    )
    moved = sum(
        1 for curve, n in zip(sweep.curves, lengths) if len(curve) != n
    )
    scores = [min(c) if c else float("inf") for c in sweep.curves]
    best = sweep.trial_ids[int(np.argmin(scores))]
    return moved, int(best != sweep.best_trial_id)


def _looked_at(run, state: State):
    """(the sweep, the trials' indices, how many whole curves, what the
    program reported for them, each one's length)."""
    sweep, chosen, first_only = pick(run, state)
    got_val = [sweep.curves[i] for i in chosen] + [
        sweep.curves[i][:1] for i in first_only
    ]
    trials = chosen + first_only
    got = (got_val, [sweep.train_first[i] for i in trials])
    return sweep, trials, len(chosen), got


def check(run, state: State, **reference_kwargs):
    limits = run.cell.traffic["limits"]
    sweep, trials, whole, got = _looked_at(run, state)
    want = reference_curves(
        run, state, [sweep.configs[i] for i in trials],
        [len(c) for c in got[0]], **reference_kwargs,
    )
    moved, other_best = decisions(run, sweep)
    unfinished = sum(s.terminated.count(False) for s in state.sweeps)
    numbers = {
        **curve_gaps(got, want, whole),
        "asha_ends_moved": float(moved),
        "best_trial_differs": float(other_best),
        "trials_not_ended": float(unfinished),
    }
    print(f"[bench] check: sweep of {len(sweep.curves)} trials; whole curves "
          f"of trials {trials[:whole]}, first epochs of {len(trials) - whole} "
          f"more ({sum(map(len, got[0]))} epochs in all) against the reference",
          flush=True)
    return judge(numbers, limits)


def readings(make_run, seeds, planted: int, control, group: int = 1,
             broken: int = 0):
    """What the limits are set from, read in one process: for every seed
    one sweep of the program at the cell's own size against the reference,
    and for the first ``planted`` seeds the reference put in the program's
    place, on the same trials, in the control's precision, with half of
    every batch left out and with its state left unchanged.  Yields
    (seed, what, numbers).  ``group`` and ``broken`` are for training
    cells; every seed here has its own data."""
    import shutil

    for i, seed in enumerate(seeds):
        run = make_run(seed)
        train, val = make_data(run)
        state = State(space=_space(run), train=train, val=val)
        record, seconds = _one_sweep(run, state, 0, "sweep_000")
        shutil.rmtree(run.work_dir, ignore_errors=True)
        print(f"[bench] seed {seed}: one sweep in {seconds:.1f}s", flush=True)
        state.sweeps.append(record)
        sweep, trials, whole, got = _looked_at(run, state)
        configs = [sweep.configs[j] for j in trials]
        lengths = [len(c) for c in got[0]]
        want = reference_curves(run, state, configs, lengths)
        moved, other_best = decisions(run, sweep)
        yield seed, "sound", {
            **curve_gaps(got, want, whole), "asha_ends_moved": float(moved),
            "best_trial_differs": float(other_best),
        }
        if i >= planted:
            continue
        for what, kwargs in (
            ("control", {"quant": control}),
            ("half_batch", {"rows_used": int(run.cell.traffic["batch_size"]) // 2}),
            ("state_unchanged", {"skip_update": True}),
        ):
            planted_side = reference_curves(run, state, configs, lengths, **kwargs)
            yield seed, what, curve_gaps(planted_side, want, whole)

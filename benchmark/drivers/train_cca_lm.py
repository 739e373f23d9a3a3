"""Driver kind ``train_cca_lm``: the ``train_lm`` driver's run (a state
epoch whose checkpoint is kept, a warm-up, the window under
``time_budget_s``, the check against a plain reference from the
reference's own starting weights: ``benchmark/drivers/train_lm.py`` says
why each) for the family ``cca_moe_lm``.

``train_lm`` binds its reference, its table of leaf names and its
operation counts by name; this file binds ``benchmark/reference/
cca_moe_lm.py``, ``to_reference`` below and ``benchmark/flops_cca_lm.py``,
and takes everything that knows no family from there: the data, the state
epoch, the wait for its checkpoint, the programs' source paths, the norms
a block at a time on every core, ``init_gap``, ``rerun_gap``, ``numbers``.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmark import flops_cca_lm
from benchmark.drivers import train as train_driver
from benchmark.drivers import train_lm
from benchmark.drivers.train_lm import (  # noqa: F401 - the driver's surface
    State,
    in_thread,
    init_gap,
    make_data,
    numbers,
    release,
    rerun_gap,
    state_epoch,
    trial_config,
    whole_rows,
)
from benchmark.run import judge

FAMILY = "cca_moe_lm"


def require_program(cfg: dict) -> None:
    """Exit at once on a tree without the family (``train_lm``'s check,
    which also covers a tree from before the loss)."""
    if cfg.get("model") != FAMILY:
        raise SystemExit(f"train_cca_lm: the trial's model is "
                         f"{cfg.get('model')!r}, not {FAMILY!r}")
    train_lm.require_program(cfg)


def setup(run, data=None) -> State:
    cfg = trial_config(run)
    require_program(cfg)
    train, val = data or make_data(run)
    # Nothing of the reference runs yet; its programs compile.
    compiled = in_thread(compile_ahead, run, cfg)
    state_record, state_checkpoint = state_epoch(run, cfg, train, val)
    t0 = time.time()
    with run.annotate("warmup"):
        trial = train_lm._one_epoch(run, cfg, train, val, "warmup")
    t1 = time.time()
    # Both done before the window opens: neither works inside it.
    train_lm._wait_for_checkpoint(state_checkpoint)
    t2 = time.time()
    compiled.result()
    print(f"[bench] warm-up took {t1 - t0:.1f}s of set-up, then the state "
          f"epoch's checkpoint {t2 - t1:.1f}s and the check's programs "
          f"{time.time() - t2:.1f}s more", flush=True)
    return State(
        config=cfg, train=train, val=val, state_record=state_record,
        state_checkpoint=state_checkpoint, warm_record=dict(trial.results[0]),
    )


def window(run, state: State) -> None:
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    t = run.cell.traffic
    model_cfg = run.cell.config
    callback, stamps, _ = train_driver._result_stamps(run)
    t0 = time.perf_counter()
    analysis = train_driver._tune_run(
        run, state.config, state.train, state.val, "window",
        time_budget_s=run.seconds, callbacks=[callback],
    )
    t1 = time.perf_counter()
    trial = analysis.trials[0]
    state.window_records = [dict(r) for r in trial.results]
    epochs = len(trial.results)
    batch, steps, seq_len = (int(t["batch_size"]), int(t["steps_per_epoch"]),
                             int(t["seq_len"]))
    tokens_per_epoch = batch * steps * seq_len
    run.window_s = t1 - t0
    run.attempted = max(epochs, 1)
    run.failed = 0 if trial.status == TrialStatus.TERMINATED else 1
    run.metrics["train_tokens_per_s"] = epochs * tokens_per_epoch / (t1 - t0)
    marks = [t0] + stamps
    run.spans["epoch"] = [b - a for a, b in zip(marks, marks[1:])]
    # The evaluation batch's counts after each epoch, as ``train_lm`` reads
    # them: ``val_sequences`` = ``batch_size``, a training step's tokens.
    pairs = [float(r["moe_local_pairs"]) for r in state.window_records
             if "moe_local_pairs" in r]
    loads = [float(r["moe_load_max_over_mean"]) for r in state.window_records
             if "moe_load_max_over_mean" in r]
    mean_pairs = sum(pairs) / len(pairs) if pairs else 0.0
    if pairs:
        run.counters["expert_pairs_per_step"] = mean_pairs
        run.counters["expert_load_max_over_mean"] = sum(loads) / len(loads)
        print(f"[bench] pairs routed here by epoch: "
              f"{[round(p) for p in pairs]}; fullest held expert over the "
              f"mean one: {[round(v, 2) for v in loads]}; seconds an epoch: "
              f"{[round(v, 3) for v in run.spans['epoch']]}", flush=True)
    layers = int(model_cfg["num_hidden_layers"])
    run.facts.update(
        epochs=epochs,
        tokens=epochs * tokens_per_epoch,
        train_flops=epochs * batch * steps
        * flops_cca_lm.train_flops_per_sequence(
            model_cfg, seq_len, mean_pairs / (layers * batch)
        ),
        attention_call=dict(
            batch=batch, seq_len=seq_len,
            heads=int(model_cfg["num_attention_heads"]),
            kv_heads=int(model_cfg["num_key_value_heads"]),
            head_dim=int(model_cfg["head_dim"]),
        ),
    )
    print(f"[bench] window: {epochs} epochs, {epochs * tokens_per_epoch} "
          f"tokens in {t1 - t0:.3f}s; trial {trial.status.value}", flush=True)


# ---------------------------------------------------------------------------
# The program's tree in the reference's names


def to_reference(tree: dict) -> Dict[str, Any]:
    """The program's parameter tree (or a tree of its shape: Adam's
    moments) as the reference's flat dict.  The program stacks a layer's
    leaves under ``layers`` with the layer as their first axis (its stack
    is a scan); the reference names layer ``i``'s ``L{i}.``: a renaming
    and that cut, no leaf is reshaped."""
    layer = tree["layers"]
    at, moe, router = (layer["attention"], layer["moe"],
                       layer["moe"]["router"])
    stacked = {
        "in_norm": layer["input_norm"]["scale"],
        "post_norm": layer["post_norm"]["scale"],
        "cca.conv0_w": at["conv0_weight"], "cca.conv0_b": at["conv0_bias"],
        "cca.conv1_w": at["conv1_weight"], "cca.conv1_b": at["conv1_bias"],
        "cca.temperature": at["temperature"],
        "moe.router.down_w": router["down"]["kernel"],
        "moe.router.down_b": router["down"]["bias"],
        "moe.router.norm": router["norm"]["scale"],
        "moe.router.out_w": router["out"]["kernel"],
    }
    for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                         ("wv_shift", "v_shift_proj"), ("wo", "o_proj")):
        stacked["cca." + ours] = at[theirs]["kernel"]
    for name in ("w_gate", "w_up", "w_down"):
        stacked["moe." + name] = moe[name]
    for j, name in enumerate(sorted(k for k in router if k.startswith("hidden_"))):
        stacked[f"moe.router.h{j}_w"] = router[name]["kernel"]
        stacked[f"moe.router.h{j}_b"] = router[name]["bias"]
    out = {"embed": tree["embed_tokens"],
           "final_norm": tree["final_norm"]["scale"]}
    for i in range(len(stacked["in_norm"])):
        out.update({f"L{i}.{name}": leaf[i] for name, leaf in stacked.items()})
    return out


def _as_f32(tree) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v, np.float32) for k, v in to_reference(tree).items()}


def program_init(cfg: dict, tokens) -> Dict[str, np.ndarray]:
    """The program's own initialisation from the trial's seed, in the
    reference's names, on the host."""
    import jax.numpy as jnp

    init, rngs = train_lm._program_init(cfg, tuple(tokens.shape))
    return _as_f32(init(rngs, jnp.asarray(tokens, jnp.int32)))


def _reference_init(cfg: dict, model_cfg: dict):
    """(the reference's initialisation compiled, the key it draws under
    from the trial's seed): one program whatever the seed."""
    import jax

    from benchmark.reference import cca_moe_lm as ref

    key = ref.init_key(int(cfg["seed"]))
    return train_lm._compiled(
        ("cca_reference_init", train_lm._unseeded(model_cfg)),
        jax.jit(lambda key: ref.init_params(model_cfg, key)), key,
    ), key


def reference_init(cfg: dict, model_cfg: dict) -> Dict[str, np.ndarray]:
    """The reference's own starting weights from the trial's seed, on the
    host."""
    init, key = _reference_init(cfg, model_cfg)
    return {k: np.asarray(v, np.float32) for k, v in init(key).items()}


def compile_ahead(run, cfg: dict) -> None:
    """Every program that the check runs, compiled for the cell's sizes on
    a thread of set-up's.  Nothing of it runs here."""
    t0 = time.time()
    _reference_programs(run, None)
    _reference_init(cfg, run.cell.config)
    train_lm._program_init(cfg, (1, int(run.cell.traffic["seq_len"])))
    print(f"[bench] the check's programs compiled in {time.time() - t0:.1f}s "
          f"on a thread of set-up's", flush=True)


# ---------------------------------------------------------------------------
# The output check

def _reference_programs(run, quant, rows_used=None):
    """(step, evaluation) of the reference at the cell's sizes; the
    reference keeps the pieces they share, compiled by this call if no
    earlier one did."""
    from benchmark.reference import cca_moe_lm as ref

    t = run.cell.traffic
    kwargs = {"seq_len": int(t["seq_len"])}
    if quant is not None:
        kwargs["quant"] = quant
    rows = int(t["reference_block_rows"])
    step = ref.make_step(
        run.cell.config, int(t["batch_size"]), rows,
        int(t["num_epochs"]) * int(t["steps_per_epoch"]),
        rows_used=rows_used, **kwargs,
    )
    return step, ref.make_eval(run.cell.config, rows, **kwargs)


def reference_epoch(run, cfg: dict, train, val, params0, *, quant=None,
                    rows_used=None):
    """The first epoch from ``params0`` (host arrays, the reference's
    names) by the plain reference: losses, Adam's first moment and the
    parameters' change leaf by leaf, validation loss."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import regressor as shared

    t = run.cell.traffic
    steps = int(t["steps_per_epoch"])
    step, evaluate = _reference_programs(run, quant, rows_used)
    epoch_key = jax.random.key(
        shared.program_seed(int(cfg["seed"]), "epoch", 0),
        impl=shared.program_rng_impl(cfg),
    )
    params = {k: jnp.asarray(v) for k, v in params0.items()}
    t0 = time.time()
    params, opt, losses = shared.run_epoch(
        step, params, shared.adam_init(params),
        jnp.asarray(train.x, jnp.int32), jnp.asarray(train.y, jnp.int32),
        epoch_key, n_train=len(train), num_batches=steps,
        batch_size=int(t["batch_size"]), lr=float(cfg["learning_rate"]),
        wd=float(cfg.get("weight_decay", 0.0)),
    )
    losses.block_until_ready()
    print(f"[bench] reference: {steps} steps in {time.time() - t0:.1f}s, "
          f"losses {[round(float(l), 5) for l in losses]}", flush=True)
    val_loss = evaluate(
        params, jnp.asarray(val.x, jnp.int32), jnp.asarray(val.y, jnp.int32)
    )
    return {
        "train_loss": float(losses.mean()),
        "val_loss": float(val_loss),
        "mu": {k: np.asarray(v, np.float32) for k, v in opt["mu"].items()},
        "dparam": {k: np.asarray(v, np.float32) - params0[k]
                   for k, v in params.items()},
    }


def checkpoint_side(path: str, record: dict, params0) -> dict:
    """What an epoch of the program reported and saved, in the reference's
    terms."""
    from distributed_machine_learning_tpu.tune.checkpoint import load_checkpoint

    t0 = time.time()
    ckpt = load_checkpoint(path)
    t1 = time.time()
    mu = _as_f32(train_driver._find(ckpt["opt_state"], "mu"))
    params = ckpt["params"]
    del ckpt  # Adam's second moment goes with it: a third of the bytes
    dparam = {k: v - params0[k] for k, v in _as_f32(params).items()}
    print(f"[bench] checkpoint read, verified and decoded in {t1 - t0:.1f}s, "
          f"renamed in {time.time() - t1:.1f}s", flush=True)
    return {
        "train_loss": float(record.get("train_loss", float("nan"))),
        "val_loss": float(record.get("validation_loss", float("nan"))),
        "mu": mu,
        "dparam": dparam,
    }


def check(run, state: State):
    if not state.window_records:
        raise SystemExit("the window reported no epoch")
    model_cfg = run.cell.config
    t0 = time.time()
    params0 = reference_init(state.config, model_cfg)
    # The window's first losses beside the state epoch's checkpoint: one
    # run, if loss_rerun_gap reads 0.  Read on a thread of its own, on the
    # host, while the chip works through the reference's epochs.
    got = in_thread(
        checkpoint_side, state.state_checkpoint, state.window_records[0],
        params0,
    )
    gap0 = init_gap(
        program_init(state.config, state.train.x[:1]), params0
    )
    t1 = time.time()
    want = reference_epoch(run, state.config, state.train, state.val, params0)
    # The norms against the whole batch's reference, on the host's cores
    # while the chip runs the epoch with a row left out.
    rows = in_thread(lambda: whole_rows(got.result(), want))
    half = reference_epoch(
        run, state.config, state.train, state.val, params0,
        rows_used=int(run.cell.traffic["batch_size"]) // 2,
    )
    t2 = time.time()
    rows = rows.result()
    t3 = time.time()
    out = numbers(got.result(), want, half, rows)
    print(f"[bench] check: starting weights {t1 - t0:.1f}s, the reference's "
          f"epochs {t2 - t1:.1f}s, the checkpoint and the first norms "
          f"{t3 - t2:.1f}s more, the other norms {time.time() - t3:.1f}s",
          flush=True)
    out["init_gap"] = gap0
    out["loss_rerun_gap"] = rerun_gap(
        [state.window_records[0], state.warm_record, state.state_record]
    )
    return judge(out, run.cell.traffic["limits"])


def readings(make_run, seeds, planted: int, control, group: int = 1,
             broken: int = 0):
    """``train_lm.readings`` for this family: for every seed the numbers
    of a sound state epoch of the program; for the first ``planted`` seeds
    also those of the reference put in the program's place in the
    control's precision, and of a state left unchanged; for the first
    ``broken`` seeds those of the program itself with the second half of
    every batch left out of its loss.  Yields (seed, what, numbers)."""
    import gc

    for i, seed in enumerate(seeds):
        for what, read in _seed_readings(
            make_run(seed), control if i < planted else None, i < broken
        ):
            yield seed, what, read
        gc.collect()  # a seed's gigabytes of host arrays, before the next's


def _seed_readings(run, control, broken: bool):
    """One side at a time, each let go once read; the program's epochs
    first, while the host holds least (``train_lm._seed_readings``)."""
    import shutil

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import trainable

    cfg = trial_config(run)
    require_program(cfg)
    train, val = make_data(run)
    params0 = reference_init(cfg, run.cell.config)
    gap0 = init_gap(program_init(cfg, train.x[:1]), params0)

    def program_epoch():
        record, path = state_epoch(run, cfg, train, val)
        train_lm._wait_for_checkpoint(path)
        side = checkpoint_side(path, record, params0)
        shutil.rmtree(run.work_dir, ignore_errors=True)
        return side

    sound, faulty = program_epoch(), None
    if broken:
        get_loss = trainable.get_loss
        trainable.get_loss = train_lm._half_batch_loss(get_loss)
        try:
            faulty = program_epoch()
        finally:
            trainable.get_loss = get_loss
            tune.clear_program_cache()
    want = reference_epoch(run, cfg, train, val, params0)
    half = reference_epoch(
        run, cfg, train, val, params0,
        rows_used=int(run.cell.traffic["batch_size"]) // 2,
    )
    yield "sound", dict(numbers(sound, want, half), init_gap=gap0)
    del sound
    if faulty is not None:
        yield "half_batch", numbers(faulty, want, half)
        del faulty
    if control is not None:
        side = reference_epoch(run, cfg, train, val, params0, quant=control)
        yield "control", numbers(side, want, half)
        zeros = {k: np.zeros_like(v) for k, v in params0.items()}
        side = dict(want, mu=zeros, dparam=zeros)
        yield "unchanged", numbers(side, want, half)

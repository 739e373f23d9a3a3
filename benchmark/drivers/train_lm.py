"""Driver kind ``train_lm``: one trial of a token model through
``tune.run(train_regressor)``, no checkpoint inside the window.

The model's state fills the chip (parameters and Adam's moments of a stage
sized to it), a job of this kind saves every few hundred steps, and a 30 s
slice holds no save.  So the state that the check compares comes from
set-up, which makes two calls: a *state epoch* (one epoch from the seed
with ``checkpoint_freq`` 1, whose checkpoint is kept) and then the warm-up
epoch on the window's own configuration (``checkpoint_freq`` 0).  The
window is the warm-up's call again under ``time_budget_s``.  All three
start from the seed and run the same epoch program, so their first
epochs' losses have to be equal to the bit (``loss_rerun_gap``): that ties
what the window computed to the checkpoint that is compared.

A run has 360 s in all, so nothing waits that need not.  The writer packs,
hashes and writes the state epoch's gigabytes while the warm-up traces and
runs (as two trials of one ``tune.run`` the two do not fit the chip: the
runner keeps the first trial's state on the device while the second
starts); the check's programs compile on a thread of their own from the
start of set-up (the chip's compiler and then the chip work for the
program meanwhile); and in the check the checkpoint is read, verified and
decoded, and the norms against the whole batch's reference are taken, on
threads while the chip runs the reference's epochs.

``check`` replays the first epoch with the plain reference
(``benchmark/reference/gated_hybrid_lm.py``) from the reference's own
starting weights (its ``init_params``, drawn from the seed by the
configuration's ``assumed`` rules), and compares the window's first losses
and the state epoch's checkpoint (Adam's first moment read, the
parameters' change compared) as the ``train`` driver does, with its
arithmetic.  The program's own initialisation, renamed leaf by leaf by
``to_reference``, has to equal the reference's to the bit (``init_gap``).
"""

from __future__ import annotations

import json
import os
import re
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from benchmark import datagen_tokens, flops_lm
from benchmark.drivers import train as train_driver
from benchmark.run import judge

# How long set-up waits for the state epoch's checkpoint to be whole on
# disk: the trial returns while the writer still serializes gigabytes.
CHECKPOINT_WAIT_S = 600.0
# Entries of a leaf that one task of a pass over the leaves works through,
# and that it holds in float64 at a time.
_CHUNK = 1 << 22
_BLOCK = 1 << 18


@dataclass
class State:
    config: dict
    train: Any
    val: Any
    state_record: dict
    state_checkpoint: str
    warm_record: dict
    window_records: List[dict] = None


trial_config = train_driver.trial_config


def make_data(run, seed=None):
    from distributed_machine_learning_tpu.data.loader import Dataset

    t = run.cell.traffic
    xt, yt, xv, yv = datagen_tokens.make_sequences(
        run.seed if seed is None else seed,
        n_train=int(t["batch_size"]) * int(t["steps_per_epoch"]),
        n_val=int(t["val_sequences"]),
        seq_len=int(t["seq_len"]),
        vocab=int(run.cell.config["vocab_size"]),
    )
    return Dataset(xt, yt), Dataset(xv, yv)


def require_program(cfg: dict) -> None:
    """Exit, before any data is made, where the program lacks the model
    family or the loss (a commit from before they were added)."""
    try:
        from distributed_machine_learning_tpu.models import models
        from distributed_machine_learning_tpu.ops.losses import losses
    except ImportError as exc:
        raise SystemExit(f"train_lm: the program cannot be imported: {exc}")
    for registry, key in ((models, "model"), (losses, "loss_function")):
        if cfg[key] not in registry:
            raise SystemExit(
                f"train_lm: this program has no {key} {cfg[key]!r} "
                f"(it has {sorted(registry)}); the cell cannot run on it"
            )


def _one_epoch(run, cfg, train, val, name):
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    analysis = train_driver._tune_run(
        run, cfg, train, val, name, stop={"training_iteration": 1}
    )
    trial = analysis.trials[0]
    if trial.status != TrialStatus.TERMINATED or len(trial.results) != 1:
        raise SystemExit(
            f"{name} trial did not finish its epoch: {trial.status} "
            f"{trial.error}"
        )
    return trial


def _wait_for_checkpoint(path: str) -> None:
    """The checkpoint's manifest lands after its payload."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        manifest_path_for,
    )

    deadline = time.time() + CHECKPOINT_WAIT_S
    while not os.path.exists(manifest_path_for(path)):
        if time.time() > deadline:
            raise SystemExit(f"the state epoch's checkpoint {path} never "
                             f"became whole")
        time.sleep(0.5)


def state_epoch(run, cfg, train, val):
    """One epoch from the seed with its checkpoint kept: (record, path).
    The call returns while the writer still works (it is given 30 s and
    then left to its thread); who reads the checkpoint waits for it
    (``_wait_for_checkpoint``)."""
    from distributed_machine_learning_tpu import tune

    t0 = time.time()
    with run.annotate("state_epoch"):
        trial = _one_epoch(
            run, dict(cfg, checkpoint_freq=1), train, val, "state"
        )
        if not trial.latest_checkpoint:
            raise SystemExit("the state epoch wrote no checkpoint")
    tune.clear_program_cache()
    print(f"[bench] state epoch took {time.time() - t0:.1f}s of set-up",
          flush=True)
    return dict(trial.results[0]), trial.latest_checkpoint


def in_thread(work, *args):
    """``work(*args)`` on a thread of its own: a future of its result."""
    pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="bench")
    future = pool.submit(work, *args)
    pool.shutdown(wait=False)
    return future


def setup(run, data=None) -> State:
    cfg = trial_config(run)
    require_program(cfg)
    train, val = data or make_data(run)
    # Nothing of the reference runs yet; its programs compile.
    compiled = in_thread(compile_ahead, run, cfg)
    state_record, state_checkpoint = state_epoch(run, cfg, train, val)
    t0 = time.time()
    with run.annotate("warmup"):
        trial = _one_epoch(run, cfg, train, val, "warmup")
    t1 = time.time()
    # Both done before the window opens: neither works inside it.
    _wait_for_checkpoint(state_checkpoint)
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
    # The program's own count of the token-expert pairs routed to the
    # experts held here, all layers, in the evaluation batch after each
    # epoch: as many tokens as a training step (``val_sequences`` =
    # ``batch_size``), under the weights that the epoch left.
    pairs = [float(r["moe_local_pairs"]) for r in state.window_records
             if "moe_local_pairs" in r]
    loads = [float(r["moe_load_max_over_mean"]) for r in state.window_records
             if "moe_load_max_over_mean" in r]
    if pairs:
        run.counters["expert_pairs_per_step"] = sum(pairs) / len(pairs)
        run.counters["expert_load_max_over_mean"] = sum(loads) / len(loads)
        print(f"[bench] pairs routed here by epoch: "
              f"{[round(p) for p in pairs]}; fullest held expert over the "
              f"mean one: {[round(v, 2) for v in loads]}; seconds an epoch: "
              f"{[round(v, 3) for v in run.spans['epoch']]}", flush=True)
    layers = int(model_cfg["num_hidden_layers"])
    pairs_a_sequence_layer = (
        (sum(pairs) / len(pairs) if pairs else 0.0) / (layers * batch)
    )
    run.facts.update(
        epochs=epochs,
        tokens=epochs * tokens_per_epoch,
        train_flops=epochs * batch * steps * flops_lm.train_flops_per_sequence(
            model_cfg, seq_len, pairs_a_sequence_layer
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


# An instruction of a compiled program and the source path it was traced
# under: ``%fusion.12 = ... metadata={op_name="jit(epoch)/.../gated_delta/mul"``.
_INSTRUCTION_SOURCE = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"', re.M
)


def op_sources(devices) -> Dict[str, Dict[str, str]]:
    """{program name: {instruction name: source path}} of the programs
    loaded on the device: what ``jax.named_scope`` an operation of the
    trace ran under.  The trace names an operation by its HLO text, which
    starts with the instruction's name, and carries no source path; the
    compiled program's text does."""
    out: Dict[str, Dict[str, str]] = {}
    for executable in devices[0].client.live_executables():
        try:
            modules = executable.hlo_modules()
        except Exception as exc:  # noqa: BLE001 - a program without text
            print(f"[bench] no HLO text for one loaded program: {exc!r}",
                  flush=True)
            continue
        for module in modules:
            names = out.setdefault(module.name, {})
            for name, source in _INSTRUCTION_SOURCE.findall(module.to_string()):
                names[name] = source
    return out


def release(run, state: State) -> None:
    from distributed_machine_learning_tpu import tune

    if run.traced:
        # After the window and its trace, while the programs are loaded.
        run.facts["op_sources"] = op_sources(run.devices)
    tune.clear_program_cache()


# ---------------------------------------------------------------------------
# The program's tree in the reference's names


def to_reference(tree: dict, cfg: dict) -> Dict[str, Any]:
    """The program's parameter tree (or a tree of its shape: Adam's
    moments) as the reference's flat dict.  How the program orders a fused
    projection's columns is its own business; this table is where the
    two meet: ``in_proj_qkvz`` is [q | k | v | z], ``in_proj_ba`` [b | a],
    the convolution's channels [q | k | v], ``q_proj`` a head's query then
    its gate."""
    from benchmark.reference.gated_hybrid_lm import layer_kinds

    d = int(cfg["hidden_size"])
    key_dim = int(cfg["linear_num_key_heads"]) * int(cfg["linear_key_head_dim"])
    value_dim = (int(cfg["linear_num_value_heads"])
                 * int(cfg["linear_value_head_dim"]))
    hv = int(cfg["linear_num_value_heads"])
    h, hkv, hd = (int(cfg["num_attention_heads"]),
                  int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    out = {
        "embed": tree["embed_tokens"],
        "final_norm": tree["final_norm"]["scale"],
        "head": tree["lm_head"]["kernel"],
    }
    for i, kind in enumerate(layer_kinds(cfg)):
        layer, p = tree[f"layer_{i}"], f"L{i}."
        out[p + "in_norm"] = layer["input_norm"]["scale"]
        out[p + "post_norm"] = layer["post_norm"]["scale"]
        if kind == "linear":
            la = layer["linear_attention"]
            w, ba, conv = (la["in_proj_qkvz"]["kernel"],
                           la["in_proj_ba"]["kernel"], la["conv_weight"])
            cuts = (0, key_dim, 2 * key_dim, 2 * key_dim + value_dim,
                    2 * key_dim + 2 * value_dim)
            for name, lo, hi in zip("qkvz", cuts, cuts[1:]):
                out[p + "gdn.w" + name] = w[:, lo:hi]
            for name, lo, hi in zip("qkv", cuts, cuts[1:]):
                out[p + "gdn.conv_" + name] = conv[lo:hi]
            out[p + "gdn.wb"], out[p + "gdn.wa"] = ba[:, :hv], ba[:, hv:]
            out[p + "gdn.A_log"] = la["A_log"]
            out[p + "gdn.dt_bias"] = la["dt_bias"]
            out[p + "gdn.o_norm"] = la["norm_weight"]
            out[p + "gdn.wo"] = la["out_proj"]["kernel"]
        else:
            at = layer["attention"]
            wq = at["q_proj"]["kernel"].reshape(d, h, 2 * hd)
            out[p + "att.wq"], out[p + "att.w_gate"] = wq[..., :hd], wq[..., hd:]
            out[p + "att.wk"] = at["k_proj"]["kernel"].reshape(d, hkv, hd)
            out[p + "att.wv"] = at["v_proj"]["kernel"].reshape(d, hkv, hd)
            out[p + "att.q_norm"] = at["q_norm"]["scale"]
            out[p + "att.k_norm"] = at["k_norm"]["scale"]
            out[p + "att.wo"] = at["o_proj"]["kernel"]
        moe = layer["moe"]
        out[p + "moe.router"] = moe["router"]["kernel"]
        for name in ("w_gate", "w_up", "w_down"):
            out[p + "moe." + name] = moe[name]
        shared = moe["shared_expert"]
        out[p + "moe.shared.w_gate"] = shared["gate_proj"]["kernel"]
        out[p + "moe.shared.w_up"] = shared["up_proj"]["kernel"]
        out[p + "moe.shared.w_down"] = shared["down_proj"]["kernel"]
        out[p + "moe.shared.gate"] = moe["shared_expert_gate"]["kernel"]
    return out


_COMPILED: Dict[tuple, Any] = {}


def _compiled(key: tuple, jitted, *shapes):
    """``jitted`` compiled for ``shapes``, once a process under ``key``:
    a thread can do that ahead of the first call (``compile_ahead``)."""
    if key not in _COMPILED:
        _COMPILED[key] = jitted.lower(*shapes).compile()
    return _COMPILED[key]


def _unseeded(cfg: dict) -> str:
    return json.dumps({k: v for k, v in cfg.items() if k != "seed"},
                      sort_keys=True, default=str)


def _program_init(cfg: dict, tokens_shape: tuple):
    """(the program's initialisation compiled for tokens of that shape,
    the keys it draws under from the trial's seed)."""
    import jax
    import jax.numpy as jnp

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.utils.seeding import init_rngs_for

    model = build_model(cfg)
    rngs = init_rngs_for(int(cfg["seed"]))
    return _compiled(
        ("program_init", _unseeded(cfg), tokens_shape),
        jax.jit(lambda rngs, x: model.init(rngs, x)["params"]),
        rngs, jax.ShapeDtypeStruct(tokens_shape, jnp.int32),
    ), rngs


def program_init(cfg: dict, model_cfg: dict, tokens) -> Dict[str, np.ndarray]:
    """The program's own initialisation from the trial's seed, in the
    reference's names, on the host."""
    import jax.numpy as jnp

    init, rngs = _program_init(cfg, tuple(tokens.shape))
    params = init(rngs, jnp.asarray(tokens, jnp.int32))
    return {k: np.asarray(v, np.float32)
            for k, v in to_reference(params, model_cfg).items()}


def _reference_init(cfg: dict, model_cfg: dict):
    """(the reference's initialisation compiled, the key it draws under
    from the trial's seed): one program whatever the seed."""
    import jax

    from benchmark.reference import gated_hybrid_lm as ref

    key = ref.init_key(int(cfg["seed"]))
    return _compiled(
        ("reference_init", _unseeded(model_cfg)),
        jax.jit(lambda key: ref.init_params(model_cfg, key)), key,
    ), key


def reference_init(cfg: dict, model_cfg: dict) -> Dict[str, np.ndarray]:
    """The reference's own starting weights from the trial's seed, on the
    host.  One compiled program, as the program's initialisation is."""
    init, key = _reference_init(cfg, model_cfg)
    return {k: np.asarray(v, np.float32) for k, v in init(key).items()}


def compile_ahead(run, cfg: dict) -> None:
    """Every program that the check runs, compiled for the cell's sizes:
    the work of a thread of set-up's, while the chip's compiler and then
    the chip work for the program.  Nothing of it runs here."""
    t0 = time.time()
    _reference_programs(run, None)
    _reference_init(cfg, run.cell.config)
    _program_init(cfg, (1, int(run.cell.traffic["seq_len"])))
    print(f"[bench] the check's programs compiled in {time.time() - t0:.1f}s "
          f"on a thread of set-up's", flush=True)


def _chunks(*arrays):
    """The arrays, flattened, cut alike into pieces of ``_CHUNK`` entries."""
    flat = [a.reshape(-1) for a in arrays]
    return [tuple(a[i:i + _CHUNK] for a in flat)
            for i in range(0, flat[0].size, _CHUNK)]


def _on_every_core(work, pieces) -> list:
    """``work`` of every piece, on the host's cores at once: a pass over
    gigabytes of leaves takes a minute on one."""
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        return list(pool.map(work, pieces))


def init_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> float:
    """The largest entry-wise distance of any leaf of the program's
    initialisation from the reference's, against the reference leaf's
    largest entry: 0 where the program starts from the weights that the
    configuration's ``assumed`` rules give, of the order of 1 where a leaf
    is drawn otherwise (another spread, another key), infinite where a
    leaf is missing or of another shape."""
    if set(got) != set(want) or any(
        got[name].shape != w.shape for name, w in want.items()
    ):
        return float("inf")
    def farthest(piece):
        """(the largest distance, the reference's largest entry) of a piece."""
        g, w = piece
        blocks = range(0, w.size, _BLOCK)
        return (max(float(np.max(np.abs(g[i:i + _BLOCK] - w[i:i + _BLOCK])))
                    for i in blocks),
                max(float(np.max(np.abs(w[i:i + _BLOCK]))) for i in blocks))

    pieces = [(name, _chunks(got[name], w)) for name, w in want.items()]
    read = iter(_on_every_core(
        farthest, [pair for _, pairs in pieces for pair in pairs]
    ))
    worst = 0.0
    for _, pairs in pieces:
        far, scale = (max(v) for v in zip(*(next(read) for _ in pairs)))
        worst = max(worst, far / (scale or 1.0))
    return worst


# ---------------------------------------------------------------------------
# The output check

_EVALS: Dict[tuple, Any] = {}


def _reference_programs(run, quant, rows_used=None):
    """(step, evaluation) of the reference at the cell's sizes, compiled
    by this call if no earlier one did: one step program whatever
    ``rows_used``, one evaluation."""
    from benchmark.reference import gated_hybrid_lm as ref

    t = run.cell.traffic
    kwargs = {"seq_len": int(t["seq_len"])}
    if quant is not None:
        kwargs["quant"] = quant
    key = (run.cell.name, getattr(quant, "__name__", None), kwargs["seq_len"])
    if key not in _EVALS:
        _EVALS[key] = ref.make_eval(
            run.cell.config, int(t["reference_block_rows"]), **kwargs
        )
    step = ref.make_step(
        run.cell.config, int(t["batch_size"]), int(t["reference_block_rows"]),
        int(t["num_epochs"]) * int(t["steps_per_epoch"]),
        rows_used=rows_used, **kwargs,
    )
    return step, _EVALS[key]


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
    seed = int(cfg["seed"])
    step, evaluate = _reference_programs(run, quant, rows_used)
    epoch_key = jax.random.key(
        shared.program_seed(seed, "epoch", 0),
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


def checkpoint_side(path: str, record: dict, params0, model_cfg) -> dict:
    """What an epoch of the program reported and saved, in the reference's
    terms."""
    from distributed_machine_learning_tpu.tune.checkpoint import load_checkpoint

    def as_f32(tree):
        return {k: np.asarray(v, np.float32)
                for k, v in to_reference(tree, model_cfg).items()}

    t0 = time.time()
    ckpt = load_checkpoint(path)
    t1 = time.time()
    mu = as_f32(train_driver._find(ckpt["opt_state"], "mu"))
    params = ckpt["params"]
    del ckpt  # Adam's second moment goes with it: a third of the bytes
    dparam = {k: v - params0[k] for k, v in as_f32(params).items()}
    print(f"[bench] checkpoint read, verified and decoded in {t1 - t0:.1f}s, "
          f"renamed in {time.time() - t1:.1f}s", flush=True)
    return {
        "train_loss": float(record.get("train_loss", float("nan"))),
        "val_loss": float(record.get("validation_loss", float("nan"))),
        "mu": mu,
        "dparam": dparam,
    }


def _chunk_sums(pair):
    """(the reference's, the program's, their difference's) sum of squares
    over a piece of a leaf, in float64, a block at a time: float64 copies
    of a whole piece are fresh pages every time, five times the seconds."""
    got, want = pair
    sums = np.zeros(3)
    for i in range(0, want.size, _BLOCK):
        w = want[i:i + _BLOCK].astype(np.float64)
        g = got[i:i + _BLOCK].astype(np.float64)
        sums[0] += np.dot(w, w)
        sums[1] += np.dot(g, g)
        g -= w
        sums[2] += np.dot(g, g)
    return sums


def leaf_rows(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]):
    """``train.leaf_rows`` (for every leaf the reference's norm, the
    program's, and that of their difference, summed in float64) for trees
    of gigabytes, a piece of a leaf at a time on every core: the ``train``
    driver's own takes a minute a pair of trees here, on one core through
    float64 copies of whole leaves."""
    names = list(want)
    pieces = [(name, _chunks(got[name], want[name])) for name in names]
    sums = iter(_on_every_core(
        _chunk_sums, [pair for _, pairs in pieces for pair in pairs]
    ))
    return {
        name: tuple(float(v) for v in np.sqrt(
            np.sum([next(sums) for _ in pairs], axis=0)
        ))
        for name, pairs in pieces
    }


def whole_rows(got: dict, want: dict) -> Dict[str, dict]:
    """``leaf_rows`` of Adam's first moment and of the parameters' change
    against the reference on the whole batch."""
    return {key: leaf_rows(got[key], want[key]) for key in ("mu", "dparam")}


def numbers(got: dict, want: dict, half: dict, rows=None) -> Dict[str, float]:
    """``train.numbers`` with this file's ``leaf_rows``: every number the
    check reads, compared or not.  ``rows`` is ``whole_rows(got, want)``
    where the caller has it already."""
    out = {
        "loss_e0_gap": abs(got["train_loss"] - want["train_loss"])
        / abs(want["train_loss"]),
        "val_e0_gap": abs(got["val_loss"] - want["val_loss"])
        / abs(want["val_loss"]),
    }
    rows = rows or whole_rows(got, want)
    moment, change = rows["mu"], rows["dparam"]
    # As there: leaves whose gradient is nought to rounding in the
    # reference are left out, by a rule on the reference's own gradient.
    median = float(np.median([r[0] for r in moment.values()]))
    keep = [k for k, r in moment.items() if r[0] >= 1e-3 * median]
    for name, key, whole in (("grad_moment", "mu", moment),
                             ("param_change", "dparam", change)):
        stats = train_driver.leaf_gaps(whole, keep, name)
        stats.update(train_driver.half_ratios(
            whole, leaf_rows(got[key], half[key]), keep
        ))
        out.update({f"{name}_{stat}": v for stat, v in stats.items()})
    return out


def rerun_gap(records: List[dict]) -> float:
    """The largest relative gap of the training or validation loss of any
    of ``records`` from the first's: 0 where the runs were the same run."""
    gaps = [0.0]
    for key in ("train_loss", "validation_loss"):
        first = float(records[0][key])
        gaps += [abs(float(r[key]) - first) / abs(first) for r in records[1:]]
    return max(gaps)


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
        params0, model_cfg,
    )
    gap0 = init_gap(
        program_init(state.config, model_cfg, state.train.x[:1]), params0
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
    """What the limits are set from, read in one process: for every seed
    the numbers of a sound state epoch of the program; for the first
    ``planted`` seeds also those of the reference put in the program's
    place in the control's precision, and of a state left unchanged (the
    reference's losses with no parameter moved); for the first ``broken``
    seeds those of the program itself with the second half of every batch
    left out of its loss.  Yields (seed, what, numbers).  ``group`` is the
    ``train`` driver's and does nothing here: every seed has its own data."""
    import gc

    for i, seed in enumerate(seeds):
        for what, numbers in _seed_readings(
            make_run(seed), control if i < planted else None, i < broken
        ):
            yield seed, what, numbers
        gc.collect()  # a seed's 17 GB of host arrays, before the next's


def _half_batch_loss(get_loss):
    """The program's loss over the first half of a batch's rows: the
    fault, planted in the program."""
    def broken(name):
        loss = get_loss(name)

        def half(preds, y):
            return loss(preds[: preds.shape[0] // 2], y[: y.shape[0] // 2])

        # As the loss it stands for, so that the step hands it the same
        # predictions (tune/_regression_program.py).
        half.widens_itself = getattr(loss, "widens_itself", False)
        return half

    return broken


def _seed_readings(run, control, broken: bool):
    """One side at a time, each let go once read: every side is 5 GB of
    host memory at the timed sizes, beside the two references'.  The
    program's epochs come first, while the host holds least: an epoch's
    checkpoint passes through it three times over (snapshot, packed bytes,
    read back)."""
    import shutil

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import trainable

    cfg = trial_config(run)
    require_program(cfg)
    train, val = make_data(run)
    params0 = reference_init(cfg, run.cell.config)
    gap0 = init_gap(program_init(cfg, run.cell.config, train.x[:1]), params0)

    def program_epoch():
        record, path = state_epoch(run, cfg, train, val)
        _wait_for_checkpoint(path)
        side = checkpoint_side(path, record, params0, run.cell.config)
        shutil.rmtree(run.work_dir, ignore_errors=True)
        return side

    sound, faulty = program_epoch(), None
    if broken:
        get_loss = trainable.get_loss
        trainable.get_loss = _half_batch_loss(get_loss)
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

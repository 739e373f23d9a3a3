"""Built-in regression trainable: the reference's L2 training loop, TPU-first.

Capability parity with `train_transformer_model`
(`/root/reference/ray-tune-hpo-regression.py:260-373`) and `train_dummy_model`
(`-sample.py:88-135`): model-from-config, optimizer/loss/schedule registries,
warmup+decay LR, gradient clipping, per-epoch validation loss + MAPE — but
re-designed for XLA rather than translated:

* The whole dataset is staged to the trial's device once; an **epoch is one
  jitted program** (`lax.scan` over shuffled batches), so there are no
  per-batch host->device copies (the reference copied every batch, `:327`) and
  no per-step Python dispatch.
* The LR schedule advances per optimizer step (the reference stepped its
  step-based schedule once per epoch, `:348` — SURVEY.md §2 C15).
* Validation runs as a second jitted scan with padding+masking so shapes stay
  static for the compile cache.
* Metrics are reported **per epoch** with an attached checkpoint pytree, so
  ASHA actually gets rungs (the reference reported once at trial end, `:373`)
  and PBT/fault-recovery can restore.

The jittable program bodies (forward convention, epoch scan, masked eval,
data staging) live in ``tune/_regression_program.py``, shared with the
vmapped population runner (``tune/vectorized.py``).

Config keys (all optional unless noted): ``model`` family; model arch keys
(see models.build_model); ``optimizer``, ``learning_rate`` (required),
``weight_decay``, ``momentum``, ``gradient_clipping``; ``loss_function``;
``lr_schedule``, ``warmup_steps``, ``total_steps``; ``batch_size``;
``num_epochs``; ``seed``; ``compute_dtype`` ("bfloat16" = real mixed
precision: bf16 matmuls/activations via the model's flax dtype, float32
params/optimizer/losses — models.compute_dtype_of); ``rng_impl`` ("auto" default:
hardware RNG on TPU, threefry elsewhere — measured ~1.5x sweep throughput
on-chip at bench shapes, ops/rng.py; "threefry" forces cross-platform-reproducible
streams, "rbg" forces hardware RNG — ops/rng.py; all deterministic in the
seed, but different impls produce different trajectories).
"""

from __future__ import annotations

import time
from collections import namedtuple
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.ops.losses import TOKEN_LOSSES, get_loss
from distributed_machine_learning_tpu.ops.optimizers import (
    INJECTABLE_OPTIMIZERS,
    make_injected_optimizer,
    make_optimizer,
    set_injected_hyperparams,
)
from distributed_machine_learning_tpu.ops.rng import resolve_rng_impl
from distributed_machine_learning_tpu.ops.schedules import get_schedule
from distributed_machine_learning_tpu.tune import session
from distributed_machine_learning_tpu.tune._regression_program import (
    detect_call_convention,
    eval_metrics_from_sums,
    make_chunk_epoch_fn,
    make_chunk_eval_fn,
    make_epoch_fn,
    make_eval_fn,
    make_forward,
    make_token_eval_fn,
    stage_data,
)
from distributed_machine_learning_tpu.perf.costmodel import (
    EpochPerfAccounting,
)
from distributed_machine_learning_tpu.tune.checkpoint import restore_into
from distributed_machine_learning_tpu.utils.compile_cache import get_tracker
from distributed_machine_learning_tpu.utils.seeding import (
    fold_seed,
    init_rngs_for,
)


# ---------------------------------------------------------------------------
# What every per-trial trainable decides the same way: the resident and
# streaming loops below and ``tune/trainable_sharded.py`` all call these.


class TrialSettings(NamedTuple):
    """The config keys a trial's set-up and epoch loop read, read once."""

    num_epochs: int
    seed: int
    loss_name: str
    accum: int
    lr: float
    wd: float
    opt_name: str
    lr_schedule: str
    warmup_steps: int
    momentum: float
    gradient_clipping: float
    checkpoint_freq: int
    injected: bool
    config_total_steps: Optional[int]

    def opt_steps(self, steps_per_epoch: int, epochs: int = 1) -> int:
        # The schedule advances once per OPTIMIZER step; with accumulation
        # that is steps_per_epoch // accum per epoch, not per micro-batch.
        return epochs * max(steps_per_epoch // self.accum, 1)

    def schedule_steps(self, steps_per_epoch: int) -> int:
        if self.config_total_steps is not None:
            return max(self.config_total_steps, 1)
        return max(self.opt_steps(steps_per_epoch, self.num_epochs), 1)

    def checkpoint_due(self, epoch: int) -> bool:
        return bool(self.checkpoint_freq) and (
            (epoch + 1) % self.checkpoint_freq == 0
        )


def trial_settings(config: Dict[str, Any]) -> TrialSettings:
    accum = max(int(config.get("accumulate_grad_batches", 1)), 1)
    opt_name = str(config.get("optimizer", "adam")).lower()
    total_steps = config.get("total_steps")
    return TrialSettings(
        num_epochs=int(config.get("num_epochs", 20)),
        seed=int(config.get("seed", 0)),
        loss_name=str(config.get("loss_function", "mse")),
        accum=accum,
        lr=float(config["learning_rate"]),
        wd=float(config.get("weight_decay", 0.0)),
        opt_name=opt_name,
        lr_schedule=str(config.get("lr_schedule", "warmup_linear_decay")),
        warmup_steps=int(config.get("warmup_steps", 0)),
        momentum=float(config.get("momentum", 0.0)),
        gradient_clipping=float(config.get("gradient_clipping", 0.0)),
        checkpoint_freq=int(config.get("checkpoint_freq", 1)),
        # lr/wd as optimizer STATE, not baked HLO constants, whenever the
        # optimizer supports it: every same-architecture trial then traces
        # to IDENTICAL HLO and the persistent XLA cache serves ONE backend
        # compile to the whole cohort (per-trial compiles otherwise
        # dominate multi-trial thread-executor runs).  The baked path
        # remains for the optimizers whose chains can't inject (lamb,
        # adafactor, ...) and for gradient accumulation (MultiSteps wraps
        # the hyperparam slots); config["inject_hyperparams"]=False forces
        # it.
        injected=(
            opt_name in INJECTABLE_OPTIMIZERS
            and accum == 1
            and bool(config.get("inject_hyperparams", True))
        ),
        config_total_steps=(
            None if total_steps is None else int(total_steps)
        ),
    )


def build_optimizer(s: TrialSettings, total_steps: int, injected: bool):
    """``(tx, shape_schedule)``: the optimizer chain, injected or baked,
    and the schedule at peak 1.0.  Every registered schedule is linear in
    ``learning_rate``, so ``lr * shape_schedule(step)`` is the effective
    rate on both paths."""

    def schedule(peak):
        return get_schedule(
            s.lr_schedule,
            learning_rate=peak,
            warmup_steps=s.warmup_steps,
            total_steps=total_steps,
        )

    shape_schedule = schedule(1.0)
    if injected:
        tx = make_injected_optimizer(
            s.opt_name,
            shape_schedule,
            momentum=s.momentum,
            gradient_clipping=s.gradient_clipping,
        )
    else:
        tx = make_optimizer(
            s.opt_name,
            learning_rate=schedule(s.lr),
            weight_decay=s.wd,
            momentum=s.momentum,
            gradient_clipping=s.gradient_clipping,
            accumulate_grad_batches=s.accum,
        )
    return tx, shape_schedule


def lr_after_epoch(s, shape_schedule, total_steps, steps_per_epoch, epoch):
    """The rate the optimizer used at ``epoch``'s last step.  Optax
    schedules are jnp-based: evaluating one IS a (small) device dispatch."""
    opt_steps = s.opt_steps(steps_per_epoch, epoch + 1)
    return s.lr * float(shape_schedule(min(opt_steps, total_steps)))


def epoch_perf_accounting(
    config, x_shape, *, batch_size, steps_per_epoch, eval_rows, device,
    **program,
):
    """Per-epoch MFU accounting (perf/costmodel.py), attributed to THIS
    trial for the step-stream anomaly detector (straggler naming in
    sweeps).  ``program``: the sharded trainable's device count and AOT
    program key."""
    return EpochPerfAccounting(
        config,
        batch_size=batch_size,
        seq_len=int(x_shape[1]) if len(x_shape) == 3 else 1,
        features=int(x_shape[-1]),
        steps_per_epoch=steps_per_epoch,
        eval_rows=eval_rows,
        device=device,
        trial_id=session.current_trial_id(),
        **program,
    )


def epoch_record(
    perf_acct, device, epoch, steps_per_epoch, train_loss, lr_now, metrics,
    exec_s, *, observe_s=None, **extra,
):
    """One epoch's result line: the loop's readings, ``extra`` (what names
    the path: mesh, input mode), the metrics, the perf annotation."""
    record = {
        "epoch": epoch,
        "train_loss": train_loss,
        "lr": lr_now,
        "steps": (epoch + 1) * steps_per_epoch,
        **extra,
        **metrics,
    }
    perf_acct.annotate(record, exec_s, device=device, observe_s=observe_s)
    if "moe_local_pairs" in metrics:
        # An expert layer's routing counts (make_token_eval_fn): the
        # mean ratio is load_max_over_mean_sum over reports.
        registry = obs.get_registry()
        registry.add("moe.local_pairs", metrics["moe_local_pairs"])
        registry.add("moe.load_max_over_mean_sum",
                     metrics["moe_load_max_over_mean"])
        registry.add("moe.reports")
    return record


def _epoch_checkpoint(s, epoch, params, opt_state, batch_stats, rng_impl):
    """The state to save with ``epoch``'s report, device-held (the async
    writer's read-back overlaps the next epoch), or None when none is due."""
    if not s.checkpoint_due(epoch):
        return None
    return {
        "params": params,
        "opt_state": opt_state,
        "batch_stats": batch_stats,
        "epoch": epoch,
        # Stream family the trial's epochs were drawn from; a restore on
        # another backend must keep it (_init_or_restore).  Extra key:
        # older restore templates ignore it.
        "rng_impl": rng_impl or "",
    }


_ModelPrograms = namedtuple("_ModelPrograms", [
    "model", "flag_name", "has_bn", "init_model", "forward", "evaluate",
])


def _model_programs(config, loss_name, probe_x, n_val_blocks, eval_bs,
                    abstract=False) -> _ModelPrograms:
    """The model and the programs that depend on it alone.  ``probe_x``: a
    concrete row, or with ``abstract`` a ShapeDtypeStruct (flag kwarg + BN
    detection with NOTHING allocated: an over-budget dataset often rides
    with a big model too)."""
    model = build_model(config)
    # Convention probe (fixed rng, discarded): learns the train-flag
    # kwarg and whether the family carries batch stats.
    probe, flag_name = detect_call_convention(
        model, probe_x, abstract=abstract
    )
    has_bn = "batch_stats" in probe
    init_kwargs = {flag_name: True if flag_name == "deterministic" else False}
    # Per-trial init diversity rides through the rng ARGUMENT (the
    # reference's torch trials each start from their own random init):
    # one compiled init program serves every seed.
    init_model = jax.jit(lambda rngs, x: model.init(rngs, x, **init_kwargs))
    forward = make_forward(model, flag_name, has_bn)
    evaluate = jax.jit(
        make_token_eval_fn(model, flag_name, n_val_blocks, eval_bs)
        if loss_name in TOKEN_LOSSES
        else make_eval_fn(forward, loss_name, n_val_blocks, eval_bs)
    )
    return _ModelPrograms(
        model=model, flag_name=flag_name, has_bn=has_bn,
        init_model=init_model, forward=forward, evaluate=evaluate,
    )


def _init_or_restore(bundle, s, config, sample_x, ckpt):
    """The trial's starting state: a fresh init, and over it the checkpoint
    (PBT exploit / fault retry) when there is one.

    Returns ``(params, opt_state, batch_stats, start_epoch, rng_impl, tx)``.
    ``tx`` is ``bundle.tx`` unless the checkpoint holds the baked optimizer
    layout: the caller then rebuilds its train program over the returned
    chain.
    """
    variables = bundle.init_model(init_rngs_for(s.seed), sample_x)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    opt_state = bundle.init_opt(params)
    if s.injected:
        opt_state = set_injected_hyperparams(opt_state, s.lr, s.wd)
    # Dropout PRNG implementation (ops/rng.py): defaults to the hardware
    # RNG on TPU — threefry key derivation measurably dominates small-shape
    # sweeps there — threefry elsewhere; rng_impl="threefry"/"rbg"
    # overrides.  The resolved impl is recorded in every checkpoint and a
    # restore REUSES the recorded one, so a trial restored on a different
    # backend keeps the stream family its earlier epochs were drawn from
    # instead of silently mixing trajectories ("" = jax default).
    rng_impl = resolve_rng_impl(config)
    if ckpt is None:
        return params, opt_state, batch_stats, 0, rng_impl, bundle.tx
    saved_impl = ckpt.get("rng_impl") if isinstance(ckpt, dict) else None
    if saved_impl is not None:
        rng_impl = saved_impl or None
    else:
        # Legacy checkpoint (predates impl recording): its epochs were
        # drawn under the RAW config value (no auto-resolution then),
        # so continue with exactly that — resolving anew could switch
        # stream families mid-trial (same fallback as vectorized.py).
        rng_impl = config.get("rng_impl") or None
    template = {
        "params": params,
        "opt_state": opt_state,
        "batch_stats": batch_stats,
        "epoch": 0,
    }
    tx, injected = bundle.tx, s.injected
    try:
        restored = restore_into(template, ckpt)
    except (ValueError, KeyError, TypeError, AttributeError):
        if not injected:
            raise
        # Legacy checkpoint: written by the pre-injection (baked)
        # optimizer layout — its opt_state pytree does not match the
        # InjectHyperparamsState template.  Fall back to the baked chain
        # for THIS incarnation so old experiments stay resumable (the next
        # fresh trial uses injection again).  Only the optimizer chain
        # (and the train program that closes over it) differ from the
        # cached bundle: its staged data, forward, init and eval programs
        # are reused.
        injected = False
        tx, _ = build_optimizer(s, bundle.total_steps, False)
        template["opt_state"] = jax.jit(tx.init)(params)
        restored = restore_into(template, ckpt)
    opt_state = restored["opt_state"]
    if injected:
        # PBT exploit copies a PEER's optimizer state and explore rewrites
        # config lr/wd — this trial's config values must win over whatever
        # rode in the restored hyperparam slots (the baked path achieves
        # the same by rebuilding the schedule from config).
        opt_state = set_injected_hyperparams(opt_state, s.lr, s.wd)
    return (
        restored["params"], opt_state, restored["batch_stats"],
        int(restored["epoch"]) + 1, rng_impl, tx,
    )


# ---------------------------------------------------------------------------
# Cohort program cache: ONE build (stage + trace + compile) per
# (architecture, data, device) shared by every trial of a tune.run cohort.
#
# With injected hyperparameters the staged data and every jitted program
# are trial-independent (lr/wd are state, seed enters as traced rng /
# per-epoch key arguments), yet each train_regressor call used to rebuild
# and retrace them — seconds of host work per trial on a 1-core TPU host,
# and N racing first-compiles when a cohort's threads start together.
# Construction runs under a per-key lock: the first trial builds, the
# rest of the cohort WAITS and reuses — in-process, this alone serializes
# the cohort's backend compile into exactly one.

_CohortBundle = namedtuple("_CohortBundle", [
    "data", "model", "flag_name", "has_bn", "forward", "tx", "init_model",
    "init_opt", "train_epoch", "evaluate", "shape_schedule",
    "steps_per_epoch", "total_steps",
])

_COHORT_CACHE: Dict[Any, Any] = {}
_COHORT_LOCKS: Dict[Any, Any] = {}
_COHORT_CACHE_MAX = 8
# Entries pin their staged splits in device memory: cap total staged
# bytes too (same rationale and limit as vectorized._PROGRAM_CACHE).
_COHORT_CACHE_MAX_BYTES = 256 * 1024 * 1024
_COHORT_GUARD = named_lock("trainable.cohort_guard")


def _bundle_nbytes(bundle) -> int:
    return sum(
        int(getattr(a, "nbytes", 0))
        for a in (bundle.data.x_train, bundle.data.y_train,
                  bundle.data.x_val, bundle.data.y_val)
    )


def clear_cohort_program_cache() -> None:
    """Drop every cached cohort bundle (frees their staged device data) and
    the streaming program bundles (programs only — streaming never pins
    staged splits)."""
    from distributed_machine_learning_tpu.data.pipeline import (
        clear_stream_program_cache,
    )

    with _COHORT_GUARD:
        _COHORT_CACHE.clear()
        _COHORT_LOCKS.clear()
    clear_stream_program_cache()


def _cohort_key(config, train_data, val_data, device):
    # Shared definitions: the vectorized runner's static signature (what
    # shapes a traced program) and content checksums (bit-exact below
    # 64 MB).  Function-level import — vectorized.py does not import this
    # module, but it imports half the package.
    from distributed_machine_learning_tpu.tune.vectorized import (
        _data_checksums,
        _static_signature,
    )

    sig = _static_signature(dict(config))
    try:
        hash(sig)
    except TypeError:
        sig = repr(sig)
    return (
        sig,
        _data_checksums(train_data, val_data),
        (getattr(device, "platform", "cpu"), getattr(device, "id", 0)),
    )


def _cohort_bundle_for(config, train_data, val_data, device, build):
    key = _cohort_key(config, train_data, val_data, device)
    with _COHORT_GUARD:
        bundle = _COHORT_CACHE.pop(key, None)
        if bundle is not None:
            _COHORT_CACHE[key] = bundle  # re-insert = LRU touch
            return bundle
        lock = _COHORT_LOCKS.setdefault(
            key, named_lock("trainable.cohort")
        )
    with lock:  # exactly-once build; the cohort's other trials wait here
        with _COHORT_GUARD:
            bundle = _COHORT_CACHE.get(key)
            if bundle is not None:
                return bundle
        bundle = build()
        with _COHORT_GUARD:
            _COHORT_CACHE[key] = bundle
            while len(_COHORT_CACHE) > 1 and (
                len(_COHORT_CACHE) > _COHORT_CACHE_MAX
                or sum(_bundle_nbytes(b) for b in _COHORT_CACHE.values())
                > _COHORT_CACHE_MAX_BYTES
            ):
                evicted = next(iter(_COHORT_CACHE))
                _COHORT_CACHE.pop(evicted)
                _COHORT_LOCKS.pop(evicted, None)
        return bundle


def train_regressor(
    config: Dict[str, Any],
    train_data: Optional[Dataset] = None,
    val_data: Optional[Dataset] = None,
):
    """The built-in trainable. Bind datasets with ``tune.with_parameters``."""
    if train_data is None or val_data is None:
        raise ValueError("train_regressor needs train_data/val_data bound")

    # One resolver for both the staged-input dtype and (inside build_model)
    # the model's matmul dtype — they must agree or mixed precision is a lie.
    from distributed_machine_learning_tpu.models import compute_dtype_of

    compute_dtype = compute_dtype_of(config) or jnp.float32

    lease = session.get_devices()
    device = lease[0] if lease else jax.devices()[0]

    # Input-mode resolution (data/pipeline.py): HBM-resident epochs when
    # the staged dataset fits, the double-buffered prefetch ring when it
    # does not (or when config["input_mode"]="streaming" forces it) —
    # explicit "resident" over the device budget raises rather than OOM.
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    input_mode = hostpipe.resolve_input_mode(
        config,
        hostpipe.staged_nbytes(train_data, val_data, compute_dtype),
        device,
    )
    s = trial_settings(config)
    if input_mode == "streaming":
        return _train_regressor_streaming(
            config, s, train_data, val_data, device, compute_dtype
        )

    # Hand-ended before the epoch loop (everything up to there is set-up);
    # a set-up that raises drops the span with the trial.
    setup_span = obs.span("trial.setup")

    def _build_bundle() -> _CohortBundle:
        with obs.span("trial.stage_data"):
            data = stage_data(
                train_data, val_data, int(config.get("batch_size", 32)),
                compute_dtype,
            )
        total_steps = s.schedule_steps(data.num_batches)
        tx, shape_schedule = build_optimizer(s, total_steps, s.injected)
        programs = _model_programs(
            config, s.loss_name, data.x_train[:1], data.n_val_blocks,
            data.eval_bs,
        )
        train_epoch = jax.jit(
            make_epoch_fn(
                programs.forward, tx, get_loss(s.loss_name),
                data.n_train, data.num_batches, data.batch_size,
            ),
            donate_argnums=(0, 1, 2),
        )
        return _CohortBundle(
            data=data, tx=tx, init_opt=jax.jit(tx.init),
            train_epoch=train_epoch, shape_schedule=shape_schedule,
            steps_per_epoch=data.num_batches, total_steps=total_steps,
            **programs._asdict(),
        )

    # Model, optimizer and program lookup; staging is its child on a miss.
    with obs.span("trial.build"):
        if s.injected:
            # Everything in the bundle is trial-independent under
            # injection: one build serves the whole cohort (and the
            # per-key lock makes the cohort's first backend compile
            # exactly-once in-process).
            bundle = _cohort_bundle_for(
                config, train_data, val_data, device, _build_bundle
            )
        else:
            bundle = _build_bundle()
    data = bundle.data
    steps_per_epoch = bundle.steps_per_epoch

    with obs.span("trial.init_or_restore"):
        params, opt_state, batch_stats, start_epoch, rng_impl, tx = (
            _init_or_restore(
                bundle, s, config, data.x_train[:1], session.get_checkpoint()
            )
        )
    train_epoch = bundle.train_epoch
    if tx is not bundle.tx:
        train_epoch = jax.jit(
            make_epoch_fn(
                bundle.forward, tx, get_loss(s.loss_name),
                data.n_train, data.num_batches, data.batch_size,
            ),
            donate_argnums=(0, 1, 2),
        )

    perf_acct = epoch_perf_accounting(
        config, data.x_train.shape, batch_size=data.batch_size,
        steps_per_epoch=steps_per_epoch, eval_rows=int(data.x_val.shape[0]),
        device=device,
    )
    tracker = get_tracker()
    setup_span.end()

    # ---- epoch loop: host-driven so the scheduler can interrupt ------------
    for epoch in range(start_epoch, s.num_epochs):
        with obs.span("epoch", {"epoch": epoch}):
            with obs.span("epoch.dispatch"):
                epoch_key = jax.random.key(
                    fold_seed(s.seed, "epoch", epoch), impl=rng_impl
                )
                # Before the t0/c0 stamps, so that the schedule's own
                # dispatch never counts as epoch execute time.
                lr_now = lr_after_epoch(
                    s, bundle.shape_schedule, bundle.total_steps,
                    steps_per_epoch, epoch,
                )
                c0 = tracker.thread_seconds()
                t0 = time.time()
                params, opt_state, batch_stats, train_loss = train_epoch(
                    params, opt_state, batch_stats, data.x_train,
                    data.y_train, epoch_key
                )
                metrics = bundle.evaluate(
                    params, batch_stats, data.x_val, data.y_val,
                    data.val_mask
                )
            # jit returns futures: the scalar readbacks are the sync.
            with obs.span("epoch.readback"):
                train_loss = float(train_loss)
                metrics = {k: float(v) for k, v in metrics.items()}
        # The readbacks above synced both programs; wall minus this
        # thread's compile seconds is device-execute time.
        exec_s = max(
            time.time() - t0 - (tracker.thread_seconds() - c0), 1e-9
        )
        record = epoch_record(
            perf_acct, device, epoch, steps_per_epoch, train_loss, lr_now,
            metrics, exec_s,
        )
        session.report(record, checkpoint=_epoch_checkpoint(
            s, epoch, params, opt_state, batch_stats, rng_impl
        ))

    return None


# ---------------------------------------------------------------------------
# Streaming (out-of-core) path: the double-buffered prefetch ring
# ---------------------------------------------------------------------------

_StreamBundle = namedtuple("_StreamBundle", [
    "model", "flag_name", "has_bn", "forward", "tx", "init_model",
    "init_opt", "chunk_train", "evaluate", "eval_chunk", "shape_schedule",
    "total_steps",
])


def _train_regressor_streaming(
    config: Dict[str, Any],
    s: TrialSettings,
    train_data: Dataset,
    val_data: Dataset,
    device,
    compute_dtype,
):
    """``train_regressor``'s out-of-core twin (``input_mode="streaming"``).

    Instead of staging both splits to the device once, the epoch's shuffled
    batch sequence is cut into chunks; a producer thread gathers chunk
    *k+1* on host (the SAME permutation the resident epoch program would
    draw — threefry bits are identical eager vs jit) and ``device_put``\\ s
    it into the bounded ring while the jitted chunk program consumes
    donated chunk *k*.  The chunk program's step body and PRNG key chain
    are the resident program's own (``make_chunk_epoch_fn``), so both
    modes see identical batches in identical order and finish with
    bit-identical params — the determinism contract
    ``tests/test_streaming.py`` asserts end to end.  Validation streams
    too when it exceeds the engage fraction of the budget, else it stays
    resident (bit-identical metrics with the resident path's eval
    program).
    """
    if s.loss_name in TOKEN_LOSSES:
        raise ValueError(
            f"loss_function={s.loss_name!r} is not supported under "
            "input_mode='streaming': the producer stages every array in "
            "the compute dtype (token ids included) and streamed "
            "validation is regression-only; use input_mode='resident'"
        )
    from distributed_machine_learning_tpu.compilecache import (
        chunked_program_key,
    )
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    setup_span = obs.span("trial.setup")  # as on the resident path
    hostpipe.get_host_input_counters().add("streams_engaged")

    x_np, y_np = train_data.x, train_data.y
    n_train = len(train_data)
    batch_size = int(min(int(config.get("batch_size", 32)), n_train))
    num_batches = max(n_train // batch_size, 1)
    steps_per_epoch = num_batches
    total_steps = s.schedule_steps(steps_per_epoch)

    # Chunk geometry: ring slabs sized to the device budget.
    row_nbytes = (
        int(np.prod(x_np.shape[1:], dtype=np.int64))
        * np.dtype(compute_dtype).itemsize
        + int(np.prod(y_np.shape[1:], dtype=np.int64)) * 4
    )
    plan = hostpipe.plan_chunks(
        num_batches, batch_size, row_nbytes, device=device, config=config
    )

    # Validation layout: identical padding math to stage_data (bit-equal
    # metrics when validation stays resident).
    n_val = len(val_data)
    eval_bs = int(min(max(batch_size, 1), n_val))
    n_val_pad = -(-n_val // eval_bs) * eval_bs
    n_val_blocks = n_val_pad // eval_bs
    val_nbytes = (
        n_val_pad * int(np.prod(val_data.x.shape[1:], dtype=np.int64))
        * np.dtype(compute_dtype).itemsize
        + n_val_pad * int(np.prod(val_data.y.shape[1:], dtype=np.int64)) * 4
    )
    engage_fraction = float(config.get(
        "streaming_engage_fraction", hostpipe.DEFAULT_ENGAGE_FRACTION
    ))
    val_streaming = (
        val_nbytes > engage_fraction * hostpipe.device_budget_bytes(device)
    )
    eval_plan = (
        hostpipe.plan_chunks(
            n_val_blocks, eval_bs, row_nbytes, device=device, config=config
        )
        if val_streaming
        else None
    )

    # ONE jitted chunk program serves the full chunk AND the tail (jit
    # retraces per slab shape: at most two traces per epoch geometry — the
    # chunk COUNT never shapes a trace).  Donation covers the state and the
    # consumed slab, so each chunk's staging buffers free at the boundary
    # (the ring's memory bound).
    def _chunk_train(forward, tx):
        return jax.jit(
            make_chunk_epoch_fn(forward, tx, get_loss(s.loss_name)),
            donate_argnums=(0, 1, 2, 4, 5),
        )

    def _build_stream_bundle() -> _StreamBundle:
        tx, shape_schedule = build_optimizer(s, total_steps, s.injected)
        programs = _model_programs(
            config, s.loss_name,
            jax.ShapeDtypeStruct(
                (1, *x_np.shape[1:]), np.dtype(compute_dtype)
            ),
            n_val_blocks, eval_bs, abstract=True,
        )
        return _StreamBundle(
            tx=tx, init_opt=jax.jit(tx.init),
            chunk_train=_chunk_train(programs.forward, tx),
            eval_chunk=jax.jit(
                make_chunk_eval_fn(programs.forward), donate_argnums=(2, 3, 4)
            ),
            shape_schedule=shape_schedule, total_steps=total_steps,
            **programs._asdict(),
        )

    with obs.span("trial.build"):
        if s.injected:
            # The chunked program's OWN cache identity: slab rows fold in,
            # chunk count does not (compilecache.chunked_program_key) — one
            # build per cohort under injection, same discipline as the
            # resident bundle.
            program_key = chunked_program_key(
                config,
                chunk_rows=plan.chunk_batches,
                batch_shape=[
                    [plan.chunk_batches, batch_size, *x_np.shape[1:]],
                    [plan.chunk_batches, batch_size, *y_np.shape[1:]],
                ],
                dtype=str(config.get("compute_dtype") or "float32"),
                donation=(0, 1, 2, 4, 5),
                extra={
                    "tail_rows": plan.tail_batches,
                    "val": ["streamed", eval_plan.chunk_batches]
                    if val_streaming else ["resident", n_val_blocks, eval_bs],
                    "device": [getattr(device, "platform", "cpu"),
                               int(getattr(device, "id", 0))],
                },
            )
            bundle = hostpipe.stream_bundle_for(
                program_key, _build_stream_bundle
            )
        else:
            bundle = _build_stream_bundle()

    with obs.span("trial.init_or_restore"):
        params, opt_state, batch_stats, start_epoch, rng_impl, tx = (
            _init_or_restore(
                bundle, s, config,
                jnp.asarray(x_np[:1], dtype=compute_dtype),
                session.get_checkpoint(),
            )
        )
    chunk_train = bundle.chunk_train
    if tx is not bundle.tx:
        chunk_train = _chunk_train(bundle.forward, tx)

    # Resident validation staging (the common case: train dominates).
    xv = yv = vmask = None
    if not val_streaming:
        pad = n_val_pad - n_val
        xv_np = (
            np.concatenate([val_data.x,
                            np.zeros((pad, *val_data.x.shape[1:]),
                                     val_data.x.dtype)])
            if pad else val_data.x
        )
        yv_np = (
            np.concatenate([val_data.y,
                            np.zeros((pad, *val_data.y.shape[1:]),
                                     val_data.y.dtype)])
            if pad else val_data.y
        )
        xv = jnp.asarray(xv_np, dtype=compute_dtype)
        yv = jnp.asarray(yv_np, dtype=jnp.float32)
        vmask = jnp.asarray(np.concatenate(
            [np.ones(n_val, np.float32), np.zeros(pad, np.float32)]
        ))

    perf_acct = epoch_perf_accounting(
        config, x_np.shape, batch_size=batch_size,
        steps_per_epoch=steps_per_epoch, eval_rows=n_val, device=device,
    )
    tracker = get_tracker()

    # ---- the producer: host gather + device_put of chunk k+1 ---------------
    depth = hostpipe.prefetch_depth(config)
    deadline_s = float(config.get(
        "streaming_producer_deadline_s", hostpipe.DEFAULT_PRODUCER_DEADLINE_S
    ))

    def _stage(arr, dtype):
        return jax.device_put(np.asarray(arr, dtype=dtype), device)

    def _epoch_perm(epoch: int) -> np.ndarray:
        # EXACTLY the resident epoch program's permutation: same key
        # derivation, same split, same truncation — threefry bits are
        # identical eager vs jit, so the host replays the in-program draw.
        epoch_key = jax.random.key(
            fold_seed(s.seed, "epoch", epoch), impl=rng_impl
        )
        perm_key, _ = jax.random.split(epoch_key)
        perm = np.asarray(jax.random.permutation(perm_key, n_train))
        return perm[: num_batches * batch_size]

    def _source():
        for epoch in range(start_epoch, s.num_epochs):
            perm = _epoch_perm(epoch)
            for start, rows in plan.chunk_sizes():
                idx = perm[start * batch_size:(start + rows) * batch_size]
                xg, yg = hostpipe.gather_batches(
                    x_np, y_np, idx, rows, batch_size
                )
                yield (
                    _stage(xg, compute_dtype), _stage(yg, np.float32)
                )
            if val_streaming:
                vmask_np = (
                    np.arange(n_val_pad) < n_val
                ).astype(np.float32)
                for vstart, vrows in eval_plan.chunk_sizes():
                    lo, hi = vstart * eval_bs, (vstart + vrows) * eval_bs
                    xvc = np.zeros(
                        (hi - lo, *val_data.x.shape[1:]), val_data.x.dtype
                    )
                    yvc = np.zeros(
                        (hi - lo, *val_data.y.shape[1:]), val_data.y.dtype
                    )
                    real = max(min(hi, n_val) - lo, 0)
                    if real:
                        xvc[:real] = val_data.x[lo:lo + real]
                        yvc[:real] = val_data.y[lo:lo + real]
                    yield (
                        _stage(
                            xvc.reshape(vrows, eval_bs,
                                        *val_data.x.shape[1:]),
                            compute_dtype,
                        ),
                        _stage(
                            yvc.reshape(vrows, eval_bs,
                                        *val_data.y.shape[1:]),
                            np.float32,
                        ),
                        _stage(
                            vmask_np[lo:hi].reshape(vrows, eval_bs),
                            np.float32,
                        ),
                    )

    prefetcher = hostpipe.ChunkPrefetcher(
        _source(), depth=depth, deadline_s=deadline_s,
        name=f"stream-{session.get_trial_id()}",
    )
    setup_span.end()

    # ---- epoch loop: consume donated chunk k while k+1 stages --------------
    try:
        for epoch in range(start_epoch, s.num_epochs):
            epoch_span = obs.span(
                "epoch", {"epoch": epoch, "mode": "streaming"}
            )
            epoch_span.__enter__()
            # The resident loop's two spans; here the dispatch also holds
            # the waits on the ring and, when validation streams, its
            # per-chunk sums.
            with obs.span("epoch.dispatch"):
                epoch_key = jax.random.key(
                    fold_seed(s.seed, "epoch", epoch), impl=rng_impl
                )
                # The resident program's in-program split: perm_key (the
                # producer replays it) and the step chain's root.
                _, key = jax.random.split(epoch_key)
                lr_now = lr_after_epoch(
                    s, bundle.shape_schedule, total_steps, steps_per_epoch,
                    epoch,
                )
                wait0 = prefetcher.wait_s
                c0 = tracker.thread_seconds()
                t0 = time.time()
                loss_parts = []
                for _start, _rows in plan.chunk_sizes():
                    xb, yb = prefetcher.get()
                    params, opt_state, batch_stats, key, losses = (
                        chunk_train(
                            params, opt_state, batch_stats, key, xb, yb
                        )
                    )
                    loss_parts.append(losses)
                    # A consumed chunk IS progress: a slow producer must
                    # read as slow, never as a silent (stalled) trial.
                    session.heartbeat()
                if val_streaming:
                    sums = np.zeros(5, np.float64)
                    for _vstart, _vrows in eval_plan.chunk_sizes():
                        xbv, ybv, mbv = prefetcher.get()
                        part = bundle.eval_chunk(
                            params, batch_stats, xbv, ybv, mbv
                        )
                        sums += np.array([float(v) for v in part])
                        session.heartbeat()
                    metrics = eval_metrics_from_sums(s.loss_name, *sums)
                else:
                    metrics = bundle.evaluate(
                        params, batch_stats, xv, yv, vmask
                    )
            # Scalar readbacks sync every queued chunk program before the
            # epoch clock stops (jit returns futures).
            with obs.span("epoch.readback"):
                train_loss = float(jnp.concatenate(loss_parts).mean())
                metrics = {k: float(v) for k, v in metrics.items()}
            wait_s = prefetcher.wait_s - wait0
            wall = time.time() - t0
            compile_s = tracker.thread_seconds() - c0
            prefetcher.note_consume(max(wall - wait_s, 0.0))
            # ``observe_s`` is wall minus compile but INCLUDING prefetch
            # wait: a starved consumer must read as slow to the anomaly
            # detector (that is the straggler signal a chaos
            # slow-producer run exists to surface), while the MFU
            # numerator keeps the wait-free exec_s.
            record = epoch_record(
                perf_acct, device, epoch, steps_per_epoch, train_loss,
                lr_now, metrics, max(wall - compile_s - wait_s, 1e-9),
                observe_s=max(wall - compile_s, 1e-9),
                input_mode="streaming",
            )
            checkpoint = _epoch_checkpoint(
                s, epoch, params, opt_state, batch_stats, rng_impl
            )
            # Close the epoch span before report (report blocks on the
            # scheduler; that wait is dispatch time, not epoch time).  An
            # exception above leaves it OPEN on purpose: a stall dump then
            # shows the in-flight epoch as the hang site.
            epoch_span.__exit__(None, None, None)
            session.report(record, checkpoint=checkpoint)
    finally:
        # Early stop, crash, or clean finish: the producer thread and the
        # ring's staged slabs must never outlive the trial.
        prefetcher.close()

    return None

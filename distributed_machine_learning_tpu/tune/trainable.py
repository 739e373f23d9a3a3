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

import threading
from collections import namedtuple
from typing import Any, Dict, Optional

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
    per_example_losses,
    stage_data,
)
from distributed_machine_learning_tpu.perf.costmodel import (
    EpochPerfAccounting,
)
from distributed_machine_learning_tpu.tune.checkpoint import restore_into
from distributed_machine_learning_tpu.utils.compile_cache import get_tracker
from distributed_machine_learning_tpu.utils.dispatch import (
    dispatch_lock,
    serialization_on,
)
from distributed_machine_learning_tpu.utils.seeding import (
    fold_seed,
    init_rngs_for,
)

# Back-compat aliases (vectorized.py and external users imported these names).
_detect_call_convention = detect_call_convention
_per_example_losses = per_example_losses


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
        # The build stages data and compiles through the backend; in a
        # MIXED-architecture cohort it can otherwise overlap another
        # architecture's epoch dispatches (utils/dispatch.py; ordering
        # is always cohort lock -> dispatch lock, never the reverse, so
        # no cycle with the epoch path which takes only dispatch_lock).
        with dispatch_lock():
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

    num_epochs = int(config.get("num_epochs", 20))
    seed = int(config.get("seed", 0))
    loss_name = str(config.get("loss_function", "mse"))
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
    if input_mode == "streaming":
        return _train_regressor_streaming(
            config, train_data, val_data, device, compute_dtype
        )

    # Hand-ended before the epoch loop (everything up to there is set-up);
    # a set-up that raises drops the span with the trial.
    setup_span = obs.span("trial.setup")
    accum = max(int(config.get("accumulate_grad_batches", 1)), 1)
    lr = float(config["learning_rate"])
    wd = float(config.get("weight_decay", 0.0))
    opt_name = str(config.get("optimizer", "adam")).lower()
    # lr/wd as optimizer STATE, not baked HLO constants, whenever the
    # optimizer supports it: every same-architecture trial then traces to
    # IDENTICAL HLO and the persistent XLA cache serves ONE backend
    # compile to the whole cohort (per-trial compiles otherwise dominate
    # multi-trial thread-executor runs).  The legacy baked path
    # remains for the optimizers whose chains can't inject (lamb,
    # adafactor, ...) and for gradient accumulation (MultiSteps wraps the
    # hyperparam slots); config["inject_hyperparams"]=False forces it.
    injected = (
        opt_name in INJECTABLE_OPTIMIZERS
        and accum == 1
        and bool(config.get("inject_hyperparams", True))
    )

    def _build_bundle(use_injected) -> _CohortBundle:
        with obs.span("trial.stage_data"):
            data = stage_data(
                train_data, val_data, int(config.get("batch_size", 32)),
                compute_dtype,
            )
        steps_per_epoch = data.num_batches
        # The schedule advances once per OPTIMIZER step; with accumulation
        # that is steps_per_epoch // accum per epoch, not per micro-batch.
        total_steps = max(int(config.get(
            "total_steps", num_epochs * max(steps_per_epoch // accum, 1)
        )), 1)
        shape_schedule = get_schedule(
            str(config.get("lr_schedule", "warmup_linear_decay")),
            learning_rate=1.0,
            warmup_steps=int(config.get("warmup_steps", 0)),
            total_steps=total_steps,
        )
        if use_injected:
            tx = make_injected_optimizer(
                opt_name,
                shape_schedule,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(
                    config.get("gradient_clipping", 0.0)
                ),
            )
        else:
            tx = make_optimizer(
                opt_name,
                learning_rate=get_schedule(
                    str(config.get("lr_schedule", "warmup_linear_decay")),
                    learning_rate=lr,
                    warmup_steps=int(config.get("warmup_steps", 0)),
                    total_steps=total_steps,
                ),
                weight_decay=wd,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(
                    config.get("gradient_clipping", 0.0)
                ),
                accumulate_grad_batches=accum,
            )
        model = build_model(config)
        # Convention probe (fixed rng, discarded): learns the train-flag
        # kwarg and whether the family carries batch stats.
        probe, flag_name = detect_call_convention(model, data.x_train[:1])
        has_bn = "batch_stats" in probe
        init_kwargs = {
            flag_name: True if flag_name == "deterministic" else False
        }
        # Per-trial init diversity rides through the rng ARGUMENT (the
        # reference's torch trials each start from their own random
        # init): one compiled init program serves every seed.
        init_model = jax.jit(
            lambda rngs, x: model.init(rngs, x, **init_kwargs)
        )
        forward = make_forward(model, flag_name, has_bn)
        train_epoch = jax.jit(
            make_epoch_fn(
                forward, tx, get_loss(loss_name),
                data.n_train, data.num_batches, data.batch_size,
            ),
            donate_argnums=(0, 1, 2),
        )
        evaluate = jax.jit(
            make_token_eval_fn(
                model, flag_name, data.n_val_blocks, data.eval_bs
            )
            if loss_name in TOKEN_LOSSES
            else make_eval_fn(
                forward, loss_name, data.n_val_blocks, data.eval_bs
            )
        )
        return _CohortBundle(
            data=data, model=model, flag_name=flag_name, has_bn=has_bn,
            forward=forward, tx=tx, init_model=init_model,
            init_opt=jax.jit(tx.init), train_epoch=train_epoch,
            evaluate=evaluate, shape_schedule=shape_schedule,
            steps_per_epoch=steps_per_epoch, total_steps=total_steps,
        )

    # Model, optimizer and program lookup; staging is its child on a miss.
    with obs.span("trial.build"):
        if injected and bool(config.get("share_programs", True)):
            # Everything in the bundle is trial-independent under
            # injection: one build serves the whole cohort (and the
            # per-key lock makes the cohort's first backend compile
            # exactly-once in-process).
            bundle = _cohort_bundle_for(
                config, train_data, val_data, device,
                lambda: _build_bundle(True),
            )
        else:
            with dispatch_lock():
                bundle = _build_bundle(injected)
    data = bundle.data
    steps_per_epoch = bundle.steps_per_epoch
    total_steps = bundle.total_steps
    shape_schedule = bundle.shape_schedule
    tx = bundle.tx
    train_epoch = bundle.train_epoch
    evaluate = bundle.evaluate

    init_span = obs.span("trial.init_or_restore")  # ends with the restore
    # Device-call section: serialized across concurrent trial threads
    # when DML_SERIALIZE_DISPATCH is on (utils/dispatch.py; off by default).
    with dispatch_lock():
        variables = bundle.init_model(init_rngs_for(seed), data.x_train[:1])
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        opt_state = bundle.init_opt(params)
        if injected:
            opt_state = set_injected_hyperparams(opt_state, lr, wd)

    # ---- restore (PBT exploit / fault retry) -------------------------------
    # Dropout PRNG implementation (ops/rng.py): defaults to the hardware
    # RNG on TPU — threefry key derivation measurably dominates small-shape
    # sweeps there — threefry elsewhere; rng_impl="threefry"/"rbg"
    # overrides.  The resolved impl is recorded in every checkpoint and a
    # restore REUSES the recorded one, so a trial restored on a different
    # backend keeps the stream family its earlier epochs were drawn from
    # instead of silently mixing trajectories ("" = jax default).
    rng_impl = resolve_rng_impl(config)
    start_epoch = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
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
        # One hold for the whole restore (including the legacy-layout
        # fallback's jit(tx.init) dispatch and retry): same coverage as
        # the sharded twin.
        with dispatch_lock():
          try:
            restored = restore_into(template, ckpt)
          except (ValueError, KeyError, TypeError, AttributeError):
            if not injected:
                raise
            # Legacy checkpoint: written by the pre-injection (baked)
            # optimizer layout — its opt_state pytree does not match the
            # InjectHyperparamsState template.  Fall back to the baked
            # chain for THIS incarnation so old experiments stay
            # resumable (the next fresh trial uses injection again).
            injected = False
            # Only the optimizer chain (and the epoch program that closes
            # over it) differ from the cached bundle — reuse its staged
            # data, forward, init, and eval programs instead of paying a
            # second stage + compile set (review r5).
            tx = make_optimizer(
                opt_name,
                learning_rate=get_schedule(
                    str(config.get("lr_schedule", "warmup_linear_decay")),
                    learning_rate=lr,
                    warmup_steps=int(config.get("warmup_steps", 0)),
                    total_steps=total_steps,
                ),
                weight_decay=wd,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(
                    config.get("gradient_clipping", 0.0)
                ),
                accumulate_grad_batches=accum,
            )
            train_epoch = jax.jit(
                make_epoch_fn(
                    bundle.forward, tx, get_loss(loss_name),
                    data.n_train, data.num_batches, data.batch_size,
                ),
                donate_argnums=(0, 1, 2),
            )
            opt_state = jax.jit(tx.init)(params)
            template["opt_state"] = opt_state
            restored = restore_into(template, ckpt)
        params = restored["params"]
        opt_state = restored["opt_state"]
        batch_stats = restored["batch_stats"]
        start_epoch = int(restored["epoch"]) + 1
        if injected:
            # PBT exploit copies a PEER's optimizer state and explore
            # rewrites config lr/wd — this trial's config values must win
            # over whatever rode in the restored hyperparam slots (the
            # baked path achieved the same by rebuilding the schedule
            # from config).
            with dispatch_lock():
                opt_state = set_injected_hyperparams(opt_state, lr, wd)
    init_span.end()

    checkpoint_freq = int(config.get("checkpoint_freq", 1))

    # ---- per-epoch MFU accounting (BASELINE.md utilization target) ---------
    # One perf-owned derivation for every trainable (perf/costmodel.py):
    # flops/peak/MFU keys stay byte-compatible with the block this
    # replaced, and each epoch's timing feeds the step-stream anomaly
    # detector attributed to THIS trial (straggler naming in sweeps).
    x_shape = data.x_train.shape
    seq_len = int(x_shape[1]) if len(x_shape) == 3 else 1
    feats = int(x_shape[-1])
    perf_acct = EpochPerfAccounting(
        config,
        batch_size=data.batch_size,
        seq_len=seq_len,
        features=feats,
        steps_per_epoch=steps_per_epoch,
        eval_rows=int(data.x_val.shape[0]),
        device=device,
        trial_id=session.current_trial_id(),
    )
    tracker = get_tracker()
    setup_span.end()

    import time as _time

    # ---- epoch loop: host-driven so the scheduler can interrupt ------------
    for epoch in range(start_epoch, num_epochs):
        step_count = (epoch + 1) * steps_per_epoch
        # The schedule is indexed by OPTIMIZER steps; with accumulation
        # that is micro-steps // accum, or the logged lr would decay
        # ``accum`` times faster than the one the optimizer actually used.
        opt_steps = (epoch + 1) * max(steps_per_epoch // accum, 1)
        # One lock hold per epoch (train + eval): the chip runs one
        # program at a time regardless (utils/dispatch.py; a no-op unless
        # serialization is on).  The key creation
        # (a small device dispatch) and the t0/c0 stamps live INSIDE
        # the hold: stamping outside would count lock-wait — other
        # trials' whole epochs — as this trial's execute time and
        # deflate mfu by ~Nx under serialization.
        with obs.span("epoch", {"epoch": epoch}), dispatch_lock():
            with obs.span("epoch.dispatch"):
                epoch_key = jax.random.key(
                    fold_seed(seed, "epoch", epoch), impl=rng_impl
                )
                # Optax schedules are jnp-based: evaluating one IS a
                # (small) device dispatch, so it rides inside the hold
                # too — placed before the t0/c0 stamps so it never counts
                # as epoch execute time.  Every registered schedule is
                # linear in learning_rate, so lr x the peak-1.0 shape IS
                # the effective rate on both the injected and baked paths.
                lr_now = lr * float(
                    shape_schedule(min(opt_steps, total_steps))
                )
                c0 = tracker.thread_seconds()
                t0 = _time.time()
                params, opt_state, batch_stats, train_loss = train_epoch(
                    params, opt_state, batch_stats, data.x_train,
                    data.y_train, epoch_key
                )
                metrics = evaluate(
                    params, batch_stats, data.x_val, data.y_val,
                    data.val_mask
                )
            # Sync INSIDE the locked section via scalar readbacks: jit
            # returns futures, so without this the lock would release
            # while the epoch still runs — the overlap the lock exists
            # to prevent.
            with obs.span("epoch.readback"):
                train_loss = float(train_loss)
                metrics = {k: float(v) for k, v in metrics.items()}
        record = {
            "epoch": epoch,
            "train_loss": train_loss,
            "lr": lr_now,
            "steps": step_count,
            **metrics,
        }
        # The in-lock readbacks above synced both programs; wall minus
        # this thread's compile seconds is device-execute time.
        exec_s = max(
            _time.time() - t0 - (tracker.thread_seconds() - c0), 1e-9
        )
        perf_acct.annotate(record, exec_s, device=device)
        if "moe_local_pairs" in metrics:
            # An expert layer's routing counts (make_token_eval_fn): the
            # mean ratio is load_max_over_mean_sum over reports.
            registry = obs.get_registry()
            registry.add("moe.local_pairs", metrics["moe_local_pairs"])
            registry.add("moe.load_max_over_mean_sum",
                         metrics["moe_load_max_over_mean"])
            registry.add("moe.reports")
        checkpoint = None
        if checkpoint_freq and (epoch + 1) % checkpoint_freq == 0:
            checkpoint = {
                "params": params,
                "opt_state": opt_state,
                "batch_stats": batch_stats,
                "epoch": epoch,
                # Stream family the trial's epochs were drawn from; a
                # restore on another backend must keep it (see restore
                # above).  Extra key: older restore templates ignore it.
                "rng_impl": rng_impl or "",
            }
            if serialization_on():
                # The async writer would otherwise read these device
                # buffers back OUTSIDE any lock, concurrent with other
                # threads' dispatches — the exact traffic pattern the
                # serialization exists to prevent.  With serialization
                # off, the device-held pytree keeps the writer's
                # readback overlapped with training (the designed
                # async-checkpoint behavior).
                with dispatch_lock():
                    checkpoint = jax.device_get(checkpoint)
        session.report(record, checkpoint=checkpoint)

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
    from distributed_machine_learning_tpu.compilecache import (
        chunked_program_key,
    )
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    counters = hostpipe.get_host_input_counters()
    counters.add("streams_engaged")

    num_epochs = int(config.get("num_epochs", 20))
    seed = int(config.get("seed", 0))
    loss_name = str(config.get("loss_function", "mse"))
    accum = max(int(config.get("accumulate_grad_batches", 1)), 1)
    lr = float(config["learning_rate"])
    wd = float(config.get("weight_decay", 0.0))
    opt_name = str(config.get("optimizer", "adam")).lower()
    injected = (
        opt_name in INJECTABLE_OPTIMIZERS
        and accum == 1
        and bool(config.get("inject_hyperparams", True))
    )

    x_np, y_np = train_data.x, train_data.y
    n_train = len(train_data)
    batch_size = int(min(int(config.get("batch_size", 32)), n_train))
    num_batches = max(n_train // batch_size, 1)
    steps_per_epoch = num_batches
    total_steps = max(int(config.get(
        "total_steps", num_epochs * max(steps_per_epoch // accum, 1)
    )), 1)

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

    def _build_stream_bundle(use_injected) -> _StreamBundle:
        shape_schedule = get_schedule(
            str(config.get("lr_schedule", "warmup_linear_decay")),
            learning_rate=1.0,
            warmup_steps=int(config.get("warmup_steps", 0)),
            total_steps=total_steps,
        )
        if use_injected:
            tx = make_injected_optimizer(
                opt_name,
                shape_schedule,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(config.get("gradient_clipping", 0.0)),
            )
        else:
            tx = make_optimizer(
                opt_name,
                learning_rate=get_schedule(
                    str(config.get("lr_schedule", "warmup_linear_decay")),
                    learning_rate=lr,
                    warmup_steps=int(config.get("warmup_steps", 0)),
                    total_steps=total_steps,
                ),
                weight_decay=wd,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(config.get("gradient_clipping", 0.0)),
                accumulate_grad_batches=accum,
            )
        model = build_model(config)
        # Abstract probe: flag kwarg + BN detection with NOTHING allocated
        # (an over-budget dataset often rides with a big model too).
        abstract_vars, flag_name = detect_call_convention(
            model,
            jax.ShapeDtypeStruct(
                (1, *x_np.shape[1:]), np.dtype(compute_dtype)
            ),
            abstract=True,
        )
        has_bn = "batch_stats" in abstract_vars
        init_kwargs = {
            flag_name: True if flag_name == "deterministic" else False
        }
        init_model = jax.jit(
            lambda rngs, x: model.init(rngs, x, **init_kwargs)
        )
        forward = make_forward(model, flag_name, has_bn)
        # ONE jitted chunk program serves the full chunk AND the tail
        # (jit retraces per slab shape: at most two traces per epoch
        # geometry — the chunk COUNT never shapes a trace).  Donation
        # covers the state and the consumed slab, so each chunk's staging
        # buffers free at the boundary (the ring's memory bound).
        chunk_train = jax.jit(
            make_chunk_epoch_fn(forward, tx, get_loss(loss_name)),
            donate_argnums=(0, 1, 2, 4, 5),
        )
        evaluate = (
            None
            if val_streaming
            else jax.jit(
                make_eval_fn(forward, loss_name, n_val_blocks, eval_bs)
            )
        )
        eval_chunk = (
            jax.jit(make_chunk_eval_fn(forward), donate_argnums=(2, 3, 4))
            if val_streaming
            else None
        )
        return _StreamBundle(
            model=model, flag_name=flag_name, has_bn=has_bn,
            forward=forward, tx=tx, init_model=init_model,
            init_opt=jax.jit(tx.init), chunk_train=chunk_train,
            evaluate=evaluate, eval_chunk=eval_chunk,
            shape_schedule=shape_schedule, total_steps=total_steps,
        )

    # The chunked program's OWN cache identity: slab rows fold in, chunk
    # count does not (compilecache.chunked_program_key) — one build per
    # cohort under injection, same discipline as the resident bundle.
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
    if injected and bool(config.get("share_programs", True)):
        with dispatch_lock():
            bundle = hostpipe.stream_bundle_for(
                program_key, lambda: _build_stream_bundle(True)
            )
    else:
        with dispatch_lock():
            bundle = _build_stream_bundle(injected)
    tx = bundle.tx
    chunk_train = bundle.chunk_train
    shape_schedule = bundle.shape_schedule

    with dispatch_lock():
        variables = bundle.init_model(
            init_rngs_for(seed),
            jnp.asarray(x_np[:1], dtype=compute_dtype),
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        opt_state = bundle.init_opt(params)
        if injected:
            opt_state = set_injected_hyperparams(opt_state, lr, wd)

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
        with dispatch_lock():
            xv = jnp.asarray(xv_np, dtype=compute_dtype)
            yv = jnp.asarray(yv_np, dtype=jnp.float32)
            vmask = jnp.asarray(np.concatenate(
                [np.ones(n_val, np.float32), np.zeros(pad, np.float32)]
            ))

    # ---- restore (PBT exploit / fault retry) -------------------------------
    rng_impl = resolve_rng_impl(config)
    start_epoch = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        saved_impl = ckpt.get("rng_impl") if isinstance(ckpt, dict) else None
        if saved_impl is not None:
            rng_impl = saved_impl or None
        else:
            rng_impl = config.get("rng_impl") or None
        template = {
            "params": params,
            "opt_state": opt_state,
            "batch_stats": batch_stats,
            "epoch": 0,
        }
        with dispatch_lock():
          try:
            restored = restore_into(template, ckpt)
          except (ValueError, KeyError, TypeError, AttributeError):
            if not injected:
                raise
            # Legacy (baked-optimizer) checkpoint: rebuild the baked chain
            # for this incarnation — same fallback as the resident path.
            injected = False
            tx = make_optimizer(
                opt_name,
                learning_rate=get_schedule(
                    str(config.get("lr_schedule", "warmup_linear_decay")),
                    learning_rate=lr,
                    warmup_steps=int(config.get("warmup_steps", 0)),
                    total_steps=total_steps,
                ),
                weight_decay=wd,
                momentum=float(config.get("momentum", 0.0)),
                gradient_clipping=float(
                    config.get("gradient_clipping", 0.0)
                ),
                accumulate_grad_batches=accum,
            )
            chunk_train = jax.jit(
                make_chunk_epoch_fn(
                    bundle.forward, tx, get_loss(loss_name)
                ),
                donate_argnums=(0, 1, 2, 4, 5),
            )
            opt_state = jax.jit(tx.init)(params)
            template["opt_state"] = opt_state
            restored = restore_into(template, ckpt)
        params = restored["params"]
        opt_state = restored["opt_state"]
        batch_stats = restored["batch_stats"]
        start_epoch = int(restored["epoch"]) + 1
        if injected:
            with dispatch_lock():
                opt_state = set_injected_hyperparams(opt_state, lr, wd)

    checkpoint_freq = int(config.get("checkpoint_freq", 1))

    # ---- per-epoch MFU accounting (same helper as the resident path) -------
    seq_len = int(x_np.shape[1]) if x_np.ndim == 3 else 1
    feats = int(x_np.shape[-1])
    perf_acct = EpochPerfAccounting(
        config,
        batch_size=batch_size,
        seq_len=seq_len,
        features=feats,
        steps_per_epoch=steps_per_epoch,
        eval_rows=n_val,
        device=device,
        trial_id=session.current_trial_id(),
    )
    tracker = get_tracker()

    # ---- the producer: host gather + device_put of chunk k+1 ---------------
    depth = hostpipe.prefetch_depth(config)
    deadline_s = float(config.get(
        "streaming_producer_deadline_s", hostpipe.DEFAULT_PRODUCER_DEADLINE_S
    ))

    def _stage(arr, dtype):
        staged = np.asarray(arr, dtype=dtype)
        if serialization_on():
            with dispatch_lock():
                return jax.device_put(staged, device)
        return jax.device_put(staged, device)

    def _epoch_perm(epoch: int) -> np.ndarray:
        # EXACTLY the resident epoch program's permutation: same key
        # derivation, same split, same truncation — threefry bits are
        # identical eager vs jit, so the host replays the in-program draw.
        if serialization_on():
            with dispatch_lock():
                epoch_key = jax.random.key(
                    fold_seed(seed, "epoch", epoch), impl=rng_impl
                )
                perm_key, _ = jax.random.split(epoch_key)
                perm = np.asarray(jax.random.permutation(perm_key, n_train))
        else:
            epoch_key = jax.random.key(
                fold_seed(seed, "epoch", epoch), impl=rng_impl
            )
            perm_key, _ = jax.random.split(epoch_key)
            perm = np.asarray(jax.random.permutation(perm_key, n_train))
        return perm[: num_batches * batch_size]

    def _source():
        for epoch in range(start_epoch, num_epochs):
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

    import time as _time

    # ---- epoch loop: consume donated chunk k while k+1 stages --------------
    try:
        for epoch in range(start_epoch, num_epochs):
            step_count = (epoch + 1) * steps_per_epoch
            opt_steps = (epoch + 1) * max(steps_per_epoch // accum, 1)
            epoch_span = obs.span(
                "epoch", {"epoch": epoch, "mode": "streaming"}
            )
            epoch_span.__enter__()
            with dispatch_lock():
                epoch_key = jax.random.key(
                    fold_seed(seed, "epoch", epoch), impl=rng_impl
                )
                # The resident program's in-program split: perm_key (the
                # producer replays it) and the step chain's root.
                _, key = jax.random.split(epoch_key)
                lr_now = lr * float(
                    shape_schedule(min(opt_steps, total_steps))
                )
            wait0 = prefetcher.wait_s
            c0 = tracker.thread_seconds()
            t0 = _time.time()
            loss_parts = []
            for _start, _rows in plan.chunk_sizes():
                # The ring get stays OUTSIDE the dispatch hold: the
                # producer's device_put takes the same lock under
                # serialization, and waiting while holding it would
                # deadlock the very overlap being measured.
                xb, yb = prefetcher.get()
                with dispatch_lock():
                    params, opt_state, batch_stats, key, losses = (
                        chunk_train(
                            params, opt_state, batch_stats, key, xb, yb
                        )
                    )
                loss_parts.append(losses)
                # A consumed chunk IS progress: a slow producer must read
                # as slow, never as a silent (stalled) trial.
                session.heartbeat()
            if val_streaming:
                sums = np.zeros(5, np.float64)
                for _vstart, _vrows in eval_plan.chunk_sizes():
                    xbv, ybv, mbv = prefetcher.get()
                    with dispatch_lock():
                        part = bundle.eval_chunk(
                            params, batch_stats, xbv, ybv, mbv
                        )
                        sums += np.array([float(v) for v in part])
                    session.heartbeat()
                metrics = eval_metrics_from_sums(loss_name, *sums)
                with dispatch_lock():
                    train_loss = float(jnp.concatenate(loss_parts).mean())
            else:
                with dispatch_lock():
                    metrics = bundle.evaluate(
                        params, batch_stats, xv, yv, vmask
                    )
                    # Scalar readbacks sync every queued chunk program
                    # before the epoch clock stops (jit returns futures).
                    train_loss = float(jnp.concatenate(loss_parts).mean())
                    metrics = {k: float(v) for k, v in metrics.items()}
            wait_s = prefetcher.wait_s - wait0
            wall = _time.time() - t0
            compile_s = tracker.thread_seconds() - c0
            exec_s = max(wall - compile_s - wait_s, 1e-9)
            prefetcher.note_consume(max(wall - wait_s, 0.0))
            record = {
                "epoch": epoch,
                "train_loss": train_loss,
                "lr": lr_now,
                "steps": step_count,
                "input_mode": "streaming",
                **metrics,
            }
            # ``observe_s`` is wall minus compile but INCLUDING prefetch
            # wait: a starved consumer must read as slow to the anomaly
            # detector (that is the straggler signal a chaos
            # slow-producer run exists to surface), while the MFU
            # numerator keeps the wait-free exec_s.
            perf_acct.annotate(
                record, exec_s, device=device,
                observe_s=max(wall - compile_s, 1e-9),
            )
            checkpoint = None
            if checkpoint_freq and (epoch + 1) % checkpoint_freq == 0:
                checkpoint = {
                    "params": params,
                    "opt_state": opt_state,
                    "batch_stats": batch_stats,
                    "epoch": epoch,
                    "rng_impl": rng_impl or "",
                }
                if serialization_on():
                    with dispatch_lock():
                        checkpoint = jax.device_get(checkpoint)
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

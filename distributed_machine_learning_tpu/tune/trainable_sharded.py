"""Built-in multi-device (sharded) regression trainable.

The multi-core-per-trial path (BASELINE config 5: N cores per trial via
``resources_per_trial={"devices": N}``).  The executor leases N devices to
the trial; this trainable builds a named mesh over exactly those devices
and runs the whole epoch as ONE jitted program:

* layouts come from the model family's **partition-rule table**
  (``models/partition_rules.py`` -> ``parallel/partition.py``), not a
  hard-coded spec table: params born sharded (abstract convention probe ->
  rule shardings -> ``out_shardings`` on the jitted init, so an over-HBM
  flagship never materializes unsharded), optimizer moments inherit the
  layout, activations pinned at the residual-stream/attention boundaries
  (``models/layers.constrain_activation`` — the model gets the mesh);
* the **fused epoch loop**: ``lax.scan`` over pre-sharded batch chunks
  inside one program, ``donate_argnums`` covering params, opt-state,
  batch-stats AND the epoch's batch arrays — N per-step dispatches
  collapse to one, donated buffers are reused in place (audited: the
  ``donation_aliased_buffers`` counter records donated inputs observed
  consumed after the first call);
* the epoch program resolves through the **AOT executable cache** under a
  ``sharded_program_key`` that folds in the mesh shape and the rule-table
  fingerprint, so sharded programs compile-once/cross-worker-dedup like
  everything else (``compilecache/``);
* BatchNorm models get synchronized BN for free: under jit the batch mean
  over a dp-sharded axis is the *global* mean (GSPMD adds the psum).

Config keys beyond ``train_regressor``'s: ``mesh_shape`` — dict of mesh
axis sizes, e.g. ``{"dp": 4}`` (default: pure dp over all leased devices)
or ``{"dp": 2, "tp": 2}`` (also settable sweep-wide via
``tune.run(mesh_shape=...)``); ``remat``/``remat_policy`` — per-block
rematerialization and its ``jax.checkpoint_policies`` name;
``partition_rules`` — per-trial rule-table override.  ``batch_size`` is
the *global* batch and must be divisible by dp.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.compilecache import (
    get_counters as get_compile_counters,
    sharded_program_key,
)
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.models.partition_rules import rules_for
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.ops.optimizers import (
    set_injected_hyperparams,
)
from distributed_machine_learning_tpu.parallel.mesh import make_mesh
from distributed_machine_learning_tpu.parallel.partition import (
    mesh_axis_sizes,
    rules_fingerprint,
)
from distributed_machine_learning_tpu.parallel.sharding import (
    opt_state_shardings,
    param_shardings,
)
from distributed_machine_learning_tpu.tune import session
from distributed_machine_learning_tpu.tune._regression_program import (
    detect_call_convention,
    make_forward,
    make_indexed_chunk_fn,
    make_indexed_epoch_fn,
    per_example_losses,
)
from distributed_machine_learning_tpu.tune.checkpoint import restore_into
from distributed_machine_learning_tpu.tune.trainable import (
    build_optimizer,
    epoch_perf_accounting,
    epoch_record,
    lr_after_epoch,
    trial_settings,
)
from distributed_machine_learning_tpu.utils.compile_cache import get_tracker
from distributed_machine_learning_tpu.utils.seeding import (
    fold_seed,
    init_rngs_for,
)


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


def _host_template(tree):
    """A restore TEMPLATE matching ``tree``'s structure/shapes/dtypes with
    no device readback: ``restore_into`` takes every value from the
    checkpoint, so zeros serve — and a process-SPANNING array (multihost
    gang trials) cannot be ``np.asarray``'d at all."""
    return jax.tree.map(
        lambda a: np.zeros(a.shape, a.dtype) if hasattr(a, "shape") else a,
        tree,
    )


@functools.lru_cache(maxsize=1)
def _epoch_aot_cache():
    """One process-wide AOT store for fused epoch programs: a second trial
    of the same shape class (or a restarted runner) deserializes the
    finished executable instead of re-tracing (``compilecache/aot.py``)."""
    from distributed_machine_learning_tpu.compilecache.aot import (
        ExecutableCache,
    )

    return ExecutableCache()


def _partitionable_threefry():
    """Scope ``jax_threefry_partitionable`` over this trainable's programs.

    Params are born sharded (``out_shardings`` on the init jit), and the
    default threefry lowering makes sharded random draws depend on the
    OUTPUT LAYOUT — the same seed would produce a different model on a
    dp×tp mesh than on pure dp (observed: tp-sharded kernels diverged,
    breaking the "TP is a layout, not a numerics change" contract).
    Partitionable threefry is jax's mesh-invariant stream: same key ⇒
    same values on any mesh, any sharding.  Scoped here (thread-local)
    so the unsharded trainables' recorded numerics stay untouched.
    """
    try:
        from jax._src.config import threefry_partitionable

        return threefry_partitionable(True)
    except Exception:  # noqa: BLE001 - private flag moved; fall through
        import contextlib

        return contextlib.nullcontext()


def train_sharded_regressor(
    config: Dict[str, Any],
    train_data: Optional[Dataset] = None,
    val_data: Optional[Dataset] = None,
):
    """Multi-device trainable. Bind datasets with ``tune.with_parameters``."""
    if train_data is None or val_data is None:
        raise ValueError("train_sharded_regressor needs train_data/val_data")
    with _partitionable_threefry():
        return _train_sharded(config, train_data, val_data)


def _train_sharded(
    config: Dict[str, Any],
    train_data: Dataset,
    val_data: Dataset,
):

    from distributed_machine_learning_tpu.multihost import runtime as mh

    n_procs = jax.process_count()
    if n_procs > 1:
        # Gang trial (multihost/): ONE mesh over every process's devices.
        # This process traces the same global program as its peers, loads
        # only the batch slices its devices address (stage_global), and
        # checkpoints only the shards it holds (host_snapshot + the
        # sharded format).  The budget probe must read a LOCAL device —
        # a peer's device has no memory stats here (dmlint DML016).
        devices = list(jax.devices())
        mesh_shape = dict(config.get("mesh_shape") or {"dp": len(devices)})
        mesh = mh.spanning_mesh(mesh_shape)
        budget_device = jax.local_devices()[0]
    else:
        devices = session.get_devices() or list(jax.devices())
        mesh_shape = dict(config.get("mesh_shape") or {"dp": len(devices)})
        mesh = make_mesh(mesh_shape, devices)
        budget_device = devices[0]
    dp = int(mesh.shape.get("dp", 1))
    rules = rules_for(config)
    rules_fp = rules_fingerprint(rules)

    s = trial_settings(config)
    global_batch = int(config.get("batch_size", 32))
    if global_batch % dp != 0:
        raise ValueError(
            f"global batch_size={global_batch} must be divisible by dp={dp}"
        )

    x_np = np.asarray(train_data.x, np.float32)
    y_np = np.asarray(train_data.y, np.float32)
    n_train = len(x_np)
    if n_train < global_batch:
        raise ValueError(
            f"train set ({n_train} rows) is smaller than the global "
            f"batch_size ({global_batch}); lower batch_size (it must stay "
            f"divisible by dp={dp})"
        )
    num_batches = n_train // global_batch
    steps_per_epoch = num_batches

    # Input-mode resolution (data/pipeline.py): the staged epoch arrays'
    # batch axis spreads over dp, so the resident footprint PER DEVICE is
    # the dataset over dp — streaming engages when even that slice
    # exceeds the engage fraction of one device's budget; explicit
    # "resident" over budget raises.
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    dataset_bytes = (
        x_np.nbytes + y_np.nbytes
        + int(val_data.x.size + val_data.y.size) * 4
    )
    if n_procs > 1 and str(config.get("input_mode") or "") == "streaming":
        raise ValueError(
            "input_mode='streaming' is not supported on a process-spanning "
            "mesh yet: the prefetch ring stages whole slabs per process "
            "and would double-buffer every host's full epoch (use "
            "'resident', or run the trial single-process)"
        )
    input_mode = hostpipe.resolve_input_mode(
        config, dataset_bytes, budget_device, shards=dp
    )
    streaming = input_mode == "streaming" and n_procs == 1
    if streaming:
        hostpipe.get_host_input_counters().add("streams_engaged")
        per_dev_row_nbytes = max(
            (int(np.prod(x_np.shape[1:], dtype=np.int64)) * 4
             + int(np.prod(y_np.shape[1:], dtype=np.int64)) * 4) // dp,
            1,
        )
        chunk_plan = hostpipe.plan_chunks(
            num_batches, global_batch, per_dev_row_nbytes,
            device=devices[0], config=config,
        )
    else:
        chunk_plan = None

    total_steps = s.schedule_steps(steps_per_epoch)
    # Same-architecture trials share ONE traced program when lr/wd ride in
    # the optimizer state instead of being baked as HLO constants
    # (tune/trainable.py trial_settings, ops/optimizers.py).
    injected = s.injected
    tx, shape_schedule = build_optimizer(s, total_steps, injected)
    loss_fn = get_loss(s.loss_name)

    # The model carries the mesh so the activation sharding constraints
    # (residual stream, attention q/k/v — models/layers.py) are live; the
    # local copy keeps Mesh objects out of the stored trial config.
    model = build_model(dict(config, mesh=mesh))
    sample_x = x_np[:1]
    repl = NamedSharding(mesh, P())

    # Abstract convention probe: flag kwarg + BN detection via
    # eval_shape — nothing allocated, so the rule shardings below
    # exist BEFORE any parameter is materialized (an over-HBM
    # flagship must be born sharded, not placed then re-placed).
    abstract_vars, flag_name = detect_call_convention(
        model, sample_x, abstract=True,
    )
    has_bn = "batch_stats" in abstract_vars
    forward = make_forward(model, flag_name, has_bn)

    p_shardings = param_shardings(
        abstract_vars["params"], mesh, rules
    )
    bs_shardings = jax.tree.map(
        lambda _: repl, abstract_vars.get("batch_stats", {})
    )
    v_shardings = jax.tree.map(lambda _: repl, abstract_vars)
    v_shardings = dict(v_shardings, params=p_shardings)
    if has_bn:
        v_shardings["batch_stats"] = bs_shardings
    init_kwargs = {
        flag_name: True if flag_name == "deterministic" else False
    }
    # Per-trial init diversity, same as train_regressor (the rng is a
    # traced argument — one compiled init program per architecture);
    # out_shardings = the rule layout, so params are born sharded.
    variables = jax.jit(
        lambda r, x: model.init(r, x, **init_kwargs),
        out_shardings=v_shardings,
    )(init_rngs_for(s.seed), sample_x)
    params = variables["params"]
    o_shardings = opt_state_shardings(
        jax.eval_shape(tx.init, params), p_shardings, mesh
    )
    opt_state = jax.jit(
        tx.init, in_shardings=(p_shardings,), out_shardings=o_shardings
    )(params)
    if injected:
        opt_state = set_injected_hyperparams(opt_state, s.lr, s.wd)
    batch_stats = variables.get("batch_stats", {})

    # Batched-epoch shardings: [num_batches, global_batch, ...] with the
    # in-batch dim over dp.
    def batched_sharding(ndim):
        return NamedSharding(mesh, P(*([None, "dp"] + [None] * (ndim - 2))))

    xb_sharding = batched_sharding(x_np.ndim + 1)
    yb_sharding = batched_sharding(y_np.ndim + 1)
    xv_sharding = NamedSharding(mesh, P("dp"))
    xb_shape = (num_batches, global_batch) + x_np.shape[1:]
    yb_shape = (num_batches, global_batch) + y_np.shape[1:]

    # Program bodies live in _regression_program.py (make_indexed_*) so the
    # jaxlint donation/hygiene audits lower the EXACT programs this
    # trainable runs; the streaming chunk twin threads the global batch
    # counter through ``i0`` so ``fold_in(epoch_key, i)`` matches the
    # resident program bit for bit across chunk boundaries.
    epoch_fn = make_indexed_epoch_fn(forward, tx, loss_fn)
    chunk_fn = make_indexed_chunk_fn(forward, tx, loss_fn)

    # The fused epoch program: donation covers EVERY large input — params
    # (0), opt_state (1), batch_stats (2), and the staged epoch batches
    # (3, 4): the batch chunks are consumed exactly once per epoch, so
    # donating them saves a full epoch-sized HBM copy per epoch.
    _EPOCH_DONATE = (0, 1, 2, 3, 4)
    # Chunk donation: state plus the consumed slab (4, 5) — each staged
    # chunk's buffers free at the chunk boundary (the ring's memory
    # bound); i0 and epoch_key are scalars.
    _CHUNK_DONATE = (0, 1, 2, 4, 5)
    # out_shardings pinned to the SAME rule layout as the inputs: without
    # the pin GSPMD may propagate a different layout onto the returned
    # params (observed: head params pulled onto 'tp' by the head-kernel
    # rule), which both breaks the next call's in_shardings contract and
    # defeats donation (an input can only alias an identically-laid-out
    # output).
    epoch_jit_kwargs = {
        "in_shardings": (
            p_shardings, o_shardings, bs_shardings,
            xb_sharding, yb_sharding, repl,
        ),
        "out_shardings": (p_shardings, o_shardings, bs_shardings, repl),
    }

    def jit_epoch():
        return jax.jit(
            epoch_fn, donate_argnums=_EPOCH_DONATE, **epoch_jit_kwargs
        )

    # AOT tier: the program key folds in mesh shape + rule-table
    # fingerprint (sharded_program_key) so a reshaped mesh or edited rule
    # table can never alias a stale executable; any resolution failure
    # degrades to the plain jit (persistent XLA cache still applies).
    program_key = sharded_program_key(
        config,
        mesh_shape=mesh_axis_sizes(mesh),
        rules_fingerprint=rules_fp,
        batch_shape=[list(xb_shape), list(yb_shape)],
        dtype=str(config.get("compute_dtype") or "float32"),
        donation=_EPOCH_DONATE,
        # A loaded executable is bound to CONCRETE devices: two same-class
        # trials leased onto different 4-device groups of one host must
        # not share an AOT entry (the collision hands trial B outputs
        # placed on trial A's devices).  Cross-worker dedup is unaffected
        # — it rides the persistent-cache/artifact-origin key, not this
        # executable-level one.  On a process-spanning mesh the PROCESS
        # TOPOLOGY folds in too: the same mesh shape decomposed over a
        # different process layout lowers different cross-process
        # collectives (reshaping the gang must split the key; the same
        # topology elsewhere must not).
        extra={
            "device_ids": [
                int(getattr(d, "id", i)) for i, d in enumerate(devices)
            ],
            **({"process_topology": mh.process_topology()}
               if n_procs > 1 else {}),
        },
    )
    chunk_jit_kwargs = {
        "in_shardings": (
            p_shardings, o_shardings, bs_shardings, repl,
            xb_sharding, yb_sharding, repl,
        ),
        "out_shardings": (p_shardings, o_shardings, bs_shardings, repl),
    }

    def jit_chunk():
        return jax.jit(
            chunk_fn, donate_argnums=_CHUNK_DONATE, **chunk_jit_kwargs
        )

    train_epoch = train_chunk = None
    if streaming:
        # Chunked programs carry their OWN cache identity: slab rows fold
        # in (the scan trip count baked into the trace), the chunk COUNT
        # does not (the host loops) — so dataset length never splits the
        # key.  One jitted callable serves full and tail slabs (jit
        # retraces per shape: at most two traces per geometry); the
        # full-slab trace resolves through the AOT tier.
        chunk_shape = (
            (chunk_plan.chunk_batches, global_batch) + x_np.shape[1:],
            (chunk_plan.chunk_batches, global_batch) + y_np.shape[1:],
        )
        chunk_key = sharded_program_key(
            config,
            mesh_shape=mesh_axis_sizes(mesh),
            rules_fingerprint=rules_fp,
            batch_shape=[list(chunk_shape[0]), list(chunk_shape[1])],
            dtype=str(config.get("compute_dtype") or "float32"),
            donation=_CHUNK_DONATE,
            extra={
                "stream_chunk_rows": chunk_plan.chunk_batches,
                "device_ids": [
                    int(getattr(d, "id", i)) for i, d in enumerate(devices)
                ],
            },
        )
        try:
            train_chunk = _epoch_aot_cache().get_or_compile(
                chunk_key, chunk_fn,
                params, opt_state, batch_stats, jnp.int32(0),
                jax.ShapeDtypeStruct(chunk_shape[0], jnp.float32),
                jax.ShapeDtypeStruct(chunk_shape[1], jnp.float32),
                jax.random.key(0),
                donate_argnums=_CHUNK_DONATE,
                jit_kwargs=chunk_jit_kwargs,
            )
        except Exception:  # noqa: BLE001 - AOT must never fail a trial
            train_chunk = jit_chunk()
        train_chunk_tail = jit_chunk() if chunk_plan.tail_batches else None
    elif n_procs > 1:
        # Process-spanning programs skip the AOT executable tier (a
        # serialized executable pins concrete devices of ONE process
        # view); compile-once still holds through the persistent XLA
        # cache + artifact origin, whose keys fold the process topology.
        train_epoch = jit_epoch()
    else:
        try:
            train_epoch = _epoch_aot_cache().get_or_compile(
                program_key, epoch_fn,
                params, opt_state, batch_stats,
                jax.ShapeDtypeStruct(xb_shape, jnp.float32),
                jax.ShapeDtypeStruct(yb_shape, jnp.float32),
                jax.random.key(0),
                donate_argnums=_EPOCH_DONATE,
                jit_kwargs=epoch_jit_kwargs,
            )
        except Exception:  # noqa: BLE001 - AOT must never fail a trial
            train_epoch = jit_epoch()

    # Eval: pad the val set to a multiple of dp, mask the padding out.
    xv_np = np.asarray(val_data.x, np.float32)
    yv_np = np.asarray(val_data.y, np.float32)
    n_val = len(xv_np)
    pad = (-n_val) % dp
    if pad:
        xv_np = np.concatenate([xv_np, np.zeros_like(xv_np[:pad])])
        yv_np = np.concatenate([yv_np, np.ones_like(yv_np[:pad])])
    mask_np = (np.arange(len(xv_np)) < n_val).astype(np.float32)

    def eval_fn(params, batch_stats, xv, yv, mask):
        preds, *_ = forward(params, batch_stats, xv, jax.random.key(0), False)
        se, ae, ape = per_example_losses(preds.astype(jnp.float32), yv)
        denom = mask.sum()
        return {
            "validation_loss": (se * mask).sum() / denom,
            "validation_mae": (ae * mask).sum() / denom,
            "validation_mape": 100.0 * (ape * mask).sum() / denom,
        }

    evaluate = jax.jit(
        eval_fn, in_shardings=(None, None, xv_sharding, xv_sharding, xv_sharding)
    )
    # stage_global = device_put single-process; on a spanning mesh each
    # process stages only its addressable slices.
    xv = mh.stage_global(xv_np, xv_sharding)
    yv = mh.stage_global(yv_np, xv_sharding)
    mask = mh.stage_global(mask_np, xv_sharding)

    # ---- restore (PBT exploit / fault retry) -------------------------------
    start_epoch = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        template = {
            "params": _host_template(params),
            "opt_state": _host_template(opt_state),
            "batch_stats": _host_template(batch_stats),
            "epoch": 0,
        }
        try:
            restored = restore_into(template, ckpt)
        except (ValueError, KeyError, TypeError, AttributeError):
            if not injected:
                raise
            # Legacy checkpoint from the pre-injection (baked) optimizer
            # layout — rebuild the baked chain for this incarnation (same
            # fallback as tune/trainable.py), then rebuild the program
            # bodies over the new `tx` and re-jit (plain jit: the AOT key
            # describes the injected layout, not this incarnation's).
            injected = False
            tx, _ = build_optimizer(s, total_steps, False)
            o_shardings = opt_state_shardings(
                jax.eval_shape(tx.init, params), p_shardings, mesh
            )
            opt_state = jax.jit(
                tx.init, in_shardings=(p_shardings,),
                out_shardings=o_shardings,
            )(params)
            epoch_fn = make_indexed_epoch_fn(forward, tx, loss_fn)
            chunk_fn = make_indexed_chunk_fn(forward, tx, loss_fn)
            epoch_jit_kwargs["in_shardings"] = (
                p_shardings, o_shardings, bs_shardings,
                xb_sharding, yb_sharding, repl,
            )
            epoch_jit_kwargs["out_shardings"] = (
                p_shardings, o_shardings, bs_shardings, repl,
            )
            chunk_jit_kwargs["in_shardings"] = (
                p_shardings, o_shardings, bs_shardings, repl,
                xb_sharding, yb_sharding, repl,
            )
            chunk_jit_kwargs["out_shardings"] = (
                p_shardings, o_shardings, bs_shardings, repl,
            )
            if streaming:
                train_chunk = jit_chunk()
                train_chunk_tail = (
                    jit_chunk() if chunk_plan.tail_batches else None
                )
            else:
                train_epoch = jit_epoch()
            template["opt_state"] = _host_template(opt_state)
            restored = restore_into(template, ckpt)
        # Re-shard restored host arrays into the live mesh layout.
        params = jax.device_put(restored["params"], p_shardings)
        opt_state = jax.device_put(restored["opt_state"], o_shardings)
        if injected:
            # This trial's config lr/wd win over restored slots (PBT
            # explore semantics — same as tune/trainable.py).
            opt_state = set_injected_hyperparams(opt_state, s.lr, s.wd)
        batch_stats = jax.device_put(
            restored["batch_stats"],
            jax.tree.map(lambda _: repl, restored["batch_stats"]),
        )
        start_epoch = int(restored["epoch"]) + 1

    # ---- per-epoch MFU/roofline accounting (perf/costmodel.py) -------------
    # The sharded paths carry their AOT program key so the captured XLA
    # cost is cross-checked against the analytic model and the records
    # report ``roofline_bound`` (process-spanning programs skip the AOT
    # tier — and the audit — by construction).
    perf_acct = epoch_perf_accounting(
        config, x_np.shape, batch_size=global_batch,
        steps_per_epoch=steps_per_epoch, eval_rows=n_val,
        device=budget_device,
        num_devices=len(devices),
        program_key=(
            chunk_key if streaming
            else program_key if n_procs == 1
            else None
        ),
        program_steps=(
            chunk_plan.chunk_batches if streaming else steps_per_epoch
        ),
    )
    tracker = get_tracker()

    def epoch_perm(epoch: int) -> np.ndarray:
        """Per-EPOCH-keyed shuffle (not one sequential stream from trial
        start): a restored incarnation resuming at epoch k must draw
        epoch k's permutation, not replay epoch 0's — the property that
        makes an interrupted+requeued trial (gang teardown, preemption)
        finish bit-identical to an uninterrupted control.  Same keying
        convention as the in-program threefry chain
        (``fold_seed(seed, "epoch", epoch)``)."""
        return np.random.default_rng(
            fold_seed(s.seed, "shuffle", epoch)
        ).permutation(n_train)[: num_batches * global_batch]

    audit_donation = True

    def count_consumed(probes):
        # Donation audit: references to donated inputs, checked for
        # consumption right after the first call — runtime proof the
        # buffer aliases took effect.
        consumed = sum(
            1 for a in probes
            if isinstance(a, jax.Array) and a.is_deleted()
        )
        if consumed:
            get_compile_counters().add("donation_aliased_buffers", consumed)

    if streaming:
        # ---- streaming epoch loop: consume chunk k while k+1 stages --------
        depth = hostpipe.prefetch_depth(config)
        deadline_s = float(config.get(
            "streaming_producer_deadline_s",
            hostpipe.DEFAULT_PRODUCER_DEADLINE_S,
        ))

        def _source():
            # The resident loop's OWN per-epoch shuffle keys, consumed in
            # the same epoch order — identical batches in identical order
            # is the determinism contract.
            for _epoch in range(start_epoch, s.num_epochs):
                perm = epoch_perm(_epoch)
                for start, rows in chunk_plan.chunk_sizes():
                    idx = perm[
                        start * global_batch:(start + rows) * global_batch
                    ]
                    xg, yg = hostpipe.gather_batches(
                        x_np, y_np, idx, rows, global_batch
                    )
                    yield (
                        jax.device_put(xg, xb_sharding),
                        jax.device_put(yg, yb_sharding),
                    )

        prefetcher = hostpipe.ChunkPrefetcher(
            _source(), depth=depth, deadline_s=deadline_s,
            name=f"stream-{session.get_trial_id()}",
        )
        try:
            for epoch in range(start_epoch, s.num_epochs):
                epoch_span = obs.span(
                    "epoch", {"epoch": epoch, "mode": "streaming"}
                )
                epoch_span.__enter__()
                epoch_key = jax.random.key(
                    fold_seed(s.seed, "epoch", epoch)
                )
                lr_now = lr_after_epoch(
                    s, shape_schedule, total_steps, steps_per_epoch, epoch
                )
                wait0 = prefetcher.wait_s
                c0 = tracker.thread_seconds()
                t0 = time.monotonic()
                loss_parts = []
                probes = None
                for start, rows in chunk_plan.chunk_sizes():
                    xb, yb = prefetcher.get()
                    if audit_donation and probes is None:
                        probes = [xb, yb] \
                            + jax.tree.leaves(params)[:1] \
                            + jax.tree.leaves(opt_state)[:1]
                    prog = (
                        train_chunk
                        if rows == chunk_plan.chunk_batches
                        else train_chunk_tail
                    )
                    params, opt_state, batch_stats, losses = prog(
                        params, opt_state, batch_stats,
                        jnp.int32(start), xb, yb, epoch_key,
                    )
                    loss_parts.append(losses)
                    # A consumed chunk IS progress for the trial watchdog.
                    session.heartbeat()
                metrics = evaluate(params, batch_stats, xv, yv, mask)
                train_loss = float(jnp.concatenate(loss_parts).mean())
                metrics = {k: float(v) for k, v in metrics.items()}
                if audit_donation and probes is not None:
                    audit_donation = False
                    count_consumed(probes)
                wait_s = prefetcher.wait_s - wait0
                wall = time.monotonic() - t0
                compile_s = tracker.thread_seconds() - c0
                prefetcher.note_consume(max(wall - wait_s, 0.0))
                # Wait rides in observe_s (a starved consumer must read
                # as slow to the anomaly detector), never in the MFU
                # numerator — same convention as tune/trainable.py.
                record = epoch_record(
                    perf_acct, budget_device, epoch, steps_per_epoch,
                    train_loss, lr_now, metrics,
                    max(wall - compile_s - wait_s, 1e-9),
                    observe_s=max(wall - compile_s, 1e-9),
                    num_devices=len(devices), mesh_shape=dict(mesh_shape),
                    input_mode="streaming",
                )
                checkpoint = None
                if s.checkpoint_due(epoch):
                    checkpoint = {
                        "params": _host(params),
                        "opt_state": _host(opt_state),
                        "batch_stats": _host(batch_stats),
                        "epoch": epoch,
                    }
                # Close before report (scheduler wait is not epoch time);
                # an exception above leaves it open — the stall dump then
                # names the in-flight epoch as the hang site.
                epoch_span.__exit__(None, None, None)
                session.report(record, checkpoint=checkpoint)
        finally:
            # Early stop, crash, or clean finish: the producer thread and
            # its staged slabs must never outlive the trial.
            prefetcher.close()
        return None

    # ---- epoch loop: host-driven so the scheduler can interrupt ------------
    for epoch in range(start_epoch, s.num_epochs):
        perm = epoch_perm(epoch)
        with obs.span("epoch", {"epoch": epoch}):
            epoch_key = jax.random.key(fold_seed(s.seed, "epoch", epoch))
            lr_now = lr_after_epoch(
                s, shape_schedule, total_steps, steps_per_epoch, epoch
            )
            # One whole-epoch slab per epoch by design (streaming is the
            # over-budget path); stage_global = device_put on one process,
            # addressable-slices-only on a spanning mesh — every host
            # gathers the same permutation, so the global batches are
            # IDENTICAL to the single-process run's (the bit-identity
            # contract).
            xb = mh.stage_global(
                x_np[perm].reshape(xb_shape), xb_sharding,
            )
            yb = mh.stage_global(
                y_np[perm].reshape(yb_shape), yb_sharding,
            )
            if audit_donation:
                probes = [xb, yb] + jax.tree.leaves(params)[:1] \
                    + jax.tree.leaves(opt_state)[:1]
            # Stamps AFTER staging (the slab transfer is input time, not
            # epoch execute time) — same MFU-clock discipline as
            # tune/trainable.py's resident loop.
            c0 = tracker.thread_seconds()
            t0 = time.monotonic()
            params, opt_state, batch_stats, train_loss = train_epoch(
                params, opt_state, batch_stats, xb, yb, epoch_key
            )
            metrics = evaluate(params, batch_stats, xv, yv, mask)
            # jit returns futures: the scalar readbacks are the sync.
            train_loss = float(train_loss)
            metrics = {k: float(v) for k, v in metrics.items()}
            exec_s = max(
                time.monotonic() - t0
                - (tracker.thread_seconds() - c0),
                1e-9,
            )
            if audit_donation:
                audit_donation = False
                count_consumed(probes)
        record = epoch_record(
            perf_acct, budget_device, epoch, steps_per_epoch, train_loss,
            lr_now, metrics, exec_s,
            num_devices=len(devices), mesh_shape=dict(mesh_shape),
        )
        if n_procs > 1 and bool(config.get("perf_gang_skew", True)):
            # Per-gang-member skew: allgather each member's epoch wall
            # and name a sustained straggler by PROCESS ID (counter +
            # flight dump — perf/anomaly.py).  One small collective per
            # epoch.
            stragglers = mh.check_gang_skew(exec_s, label="epoch")
            if stragglers:
                record["gang_stragglers"] = [
                    int(p) for p, _ in stragglers
                ]
        checkpoint = None
        if s.checkpoint_due(epoch):
            # host_snapshot copies fully-addressable leaves and leaves
            # process-SPANNING leaves sharded: each gang member then
            # serializes exactly the shards it holds (ckpt/format.py).
            checkpoint = {
                "params": mh.host_snapshot(params),
                "opt_state": mh.host_snapshot(opt_state),
                "batch_stats": mh.host_snapshot(batch_stats),
                "epoch": epoch,
            }
        session.report(record, checkpoint=checkpoint)

    return None

"""Vectorized HPO: run K trials as ONE jitted program via ``jax.vmap``.

This is the TPU-native answer to the reference's one-trial-per-GPU layout
(`/root/reference/ray-tune-hpo-regression.py:475` — ``resources_per_trial=
{"gpu": 1}``, concurrency = #GPUs).  The HPO workloads in the reference are
small (d_model ≤ 512, batch 32, seq 96): a single such trial leaves most of a
TPU chip's MXU idle.  Instead of leasing one chip per trial, this runner
**stacks trials along a population axis** and `vmap`s model init, the training
scan, and evaluation over that axis — so one chip trains K models in lockstep
inside one XLA executable, and the whole sweep amortizes exactly one compile.

What can be vectorized: hyperparameters that enter the *numerics* but not the
*program shape* — ``learning_rate``, ``weight_decay``, and ``seed`` (init +
shuffle + dropout randomness).  They ride in per-trial state: lr/wd live in
``optax.inject_hyperparams`` optimizer state, seeds become per-trial PRNG
keys.  Everything else (model family, d_model, num_layers, batch_size,
optimizer name, ...) changes the traced program, so configs are grouped by
their static signature and each group runs as its own vmapped program.

Trials are suggested and trained **chunk by chunk** (``max_batch_trials`` per
chunk): adaptive searchers (TPE, BayesOpt) see every earlier chunk's results
before proposing the next chunk, so model-based search still adapts — at
chunk granularity rather than trial granularity.

Scheduler semantics: per-epoch results are streamed trial-by-trial through the
scheduler exactly as the threaded runner does, so ASHA/median-stopping decide
on the same rung statistics.  Early stopping saves real FLOPs here too: when
survivors drop to half the population, the population is **compacted** —
stopped trials' rows are sliced out of the vmapped param/optimizer pytrees
and the remaining trials continue as a smaller program.  Compaction points
are halving boundaries, so a K-trial group compiles at most log2(K) distinct
population sizes (each cached by jit and the persistent compile cache).
Because each new size means an XLA recompile, ``compaction="auto"`` (the
default) applies a measured cost model — compact only when
``remaining_epochs x epoch_exec_time x shrink_fraction`` exceeds the
observed compile cost — so a cold compile cache never turns the FLOP saving
into a wall-clock loss ("always"/"never" override it).  Per-trial PRNG keys
travel with their rows, so a surviving trial's trajectory is independent of
who else is still in the population.

**Vectorized PBT**: with a ``PopulationBasedTraining`` scheduler, the vmapped
batch IS the PBT population — exploit is one device-side gather
(bottom-quantile rows adopt top-quantile rows' params and optimizer state)
and explore rewrites per-row learning_rate/weight_decay in the injected
optimizer hyperparams.  No stop-and-respawn, no checkpoint round-trip, no
recompile.  Two execution modes (``pbt_mode=``): **compiled** (default
where possible) scans WHOLE GENERATIONS inside one program — quantile
ranking, the exploit gather, and the PRNG-driven explore are part of the
traced computation, so a sweep of G generations costs
``ceil(num_epochs/chunk)`` host dispatches instead of one per interval
(the Podracer "Anakin" architecture applied to HPO); **boundary** keeps
the host round-trip per interval but makes the SAME decisions through the
shared deterministic reference step (``schedulers/pbt.py``), bit for bit.
Only optimizer-state hyperparams can mutate (static keys change the
program — use ``tune.run``'s respawn PBT for those).  PB2 composes on the
boundary path: its GP observes every report via ``observe_result`` and
its UCB choice rides the same gather.  Other REQUEUE-style schedulers are
unsupported.

The jittable program bodies are shared with the per-trial trainable via
``tune/_regression_program.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from distributed_machine_learning_tpu import obs as _obs
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.ops.optimizers import (
    make_injected_optimizer,
    set_injected_hyperparams,
)
from distributed_machine_learning_tpu.ops.rng import resolve_rng_impl
from distributed_machine_learning_tpu.ops.schedules import get_schedule
from distributed_machine_learning_tpu.utils.heartbeat import touch_heartbeat
from distributed_machine_learning_tpu.tune._regression_program import (
    detect_call_convention,
    make_epoch_fn,
    make_eval_fn,
    make_forward,
    stage_data,
)
from distributed_machine_learning_tpu.tune.experiment import (
    ExperimentAnalysis,
    ExperimentStore,
)
from distributed_machine_learning_tpu.tune.schedulers.base import (
    CONTINUE,
    FIFOScheduler,
    REQUEUE,
    STOP,
    TrialScheduler,
)
from distributed_machine_learning_tpu.tune.search.base import (
    RandomSearch,
    Searcher,
    maybe_warm_start,
)
from distributed_machine_learning_tpu.tune.search_space import SearchSpace
from distributed_machine_learning_tpu.tune.stoppers import resolve_stop, stop_hit
from distributed_machine_learning_tpu.tune.trial import Trial, TrialStatus
from distributed_machine_learning_tpu.utils.seeding import rng_from

# Hyperparameters that vary across trials *within* one vmapped program.
# Must agree with compilecache.NON_STRUCTURAL_KEYS: the grouping that
# decides what shares one vmapped program is the same identity the
# compile-artifact layer keys programs by.
VECTOR_KEYS = ("learning_rate", "weight_decay", "seed")

from distributed_machine_learning_tpu.compilecache import (  # noqa: E402
    NON_STRUCTURAL_KEYS as _NON_STRUCTURAL_KEYS,
)

assert frozenset(VECTOR_KEYS) == _NON_STRUCTURAL_KEYS, (
    "vectorized VECTOR_KEYS and compilecache.NON_STRUCTURAL_KEYS diverged"
)


def _static_signature(config: Dict[str, Any]) -> Tuple:
    """Hashable signature of everything that shapes the traced program."""
    items = []
    for k in sorted(config):
        if k in VECTOR_KEYS:
            continue
        v = config[k]
        items.append((k, tuple(v) if isinstance(v, list) else v))
    return tuple(items)


# Shared with the per-trial trainable (ops/optimizers.py): lr/wd live in
# the optimizer state so a population can vmap over them — and so every
# same-architecture trial traces to identical HLO.
_make_population_optimizer = make_injected_optimizer
_set_hyperparams = set_injected_hyperparams


class _GroupProgram:
    """The vmapped init/train/eval programs for one static-signature group."""

    def __init__(self, static_cfg: Dict[str, Any], train_data: Dataset,
                 val_data: Dataset, pop_sharding=None):
        cfg = static_cfg
        self._static_cfg = dict(static_cfg)
        # Canonical program identity (compilecache): what the persistent
        # XLA cache amortizes across sweeps/processes and what a cluster
        # origin would exchange — lr/wd/seed are vmapped state, so they
        # are absent by construction.
        from distributed_machine_learning_tpu.compilecache import (
            program_key as _program_key,
        )

        self.program_key = _program_key(
            self._static_cfg,
            batch_shape=[tuple(train_data.x.shape), tuple(val_data.x.shape)],
            extra={"vectorized": 1},
        )
        self.loss_name = str(cfg.get("loss_function", "mse"))
        self.num_epochs = int(cfg.get("num_epochs", 20))
        from distributed_machine_learning_tpu.models import compute_dtype_of

        compute_dtype = compute_dtype_of(cfg) or jnp.float32

        self.data = data = stage_data(
            train_data, val_data, int(cfg.get("batch_size", 32)), compute_dtype
        )
        self._data_sums = _data_checksums(train_data, val_data)
        # Measured dispatch history for epochs_per_dispatch="auto": dicts of
        # {chunk, rows, exec_s, compile_s} appended per dispatch.  Rides the
        # cross-call program cache, so a later sweep on this program (e.g.
        # an ASHA pass after a FIFO pass) decides from the earlier sweep's
        # measurements.
        self.dispatch_obs: list = []
        self.steps_per_epoch = data.num_batches
        total_steps = int(
            cfg.get("total_steps", self.num_epochs * data.num_batches)
        )
        self.total_steps = max(total_steps, 1)
        # Shape-only schedule (peak 1.0); per-trial lr scales it in the chain.
        self.shape_schedule = get_schedule(
            str(cfg.get("lr_schedule", "warmup_linear_decay")),
            learning_rate=1.0,
            warmup_steps=int(cfg.get("warmup_steps", 0)),
            total_steps=self.total_steps,
        )
        tx = self.tx = _make_population_optimizer(
            str(cfg.get("optimizer", "adam")),
            self.shape_schedule,
            float(cfg.get("momentum", 0.0)),
            float(cfg.get("gradient_clipping", 0.0)),
        )

        model = build_model(cfg)
        sample_x = data.x_train[:1]
        variables, flag_name = detect_call_convention(model, sample_x)
        self.has_bn = "batch_stats" in variables
        forward = make_forward(model, flag_name, self.has_bn)

        init_kwargs = {flag_name: True if flag_name == "deterministic" else False}

        def init_one(base_key, lr, wd):
            pk, _ = jax.random.split(base_key)
            variables = model.init(
                {"params": pk, "dropout": base_key}, sample_x, **init_kwargs
            )
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            opt_state = _set_hyperparams(tx.init(params), lr, wd)
            return params, opt_state, batch_stats

        epoch_one = make_epoch_fn(
            forward, tx, get_loss(self.loss_name),
            data.n_train, data.num_batches, data.batch_size,
        )
        eval_one = make_eval_fn(
            forward, self.loss_name, data.n_val_blocks, data.eval_bs
        )
        # Kept for the compiled-PBT generation scan, which composes the
        # same epoch/eval bodies inside its own lax.scan.
        self._epoch_one = epoch_one
        self._eval_one = eval_one
        self._pbt_programs: Dict[Tuple, Tuple] = {}
        self._param_count: Optional[int] = None

        # With a population mesh, init materializes DIRECTLY in the sharded
        # layout — device 0 never has to hold (or scatter) the whole
        # population's params/optimizer state.
        self.init_population = jax.jit(
            jax.vmap(init_one),
            out_shardings=None if pop_sharding is None else pop_sharding,
        )
        # Data is shared across the population: in_axes=None for x/y.
        self.train_epoch = jax.jit(
            jax.vmap(epoch_one, in_axes=(0, 0, 0, None, None, 0)),
            donate_argnums=(0, 1, 2),
        )
        self.eval_population = jax.jit(
            jax.vmap(eval_one, in_axes=(0, 0, None, None, None))
        )

        # Multi-epoch dispatch: scan train+eval over E epochs INSIDE one
        # program, so a chunk of epochs costs one host->device round trip
        # instead of 2E (dispatch latency dominates small models).
        # Per-epoch losses/metrics come back
        # stacked along a trailing epoch axis.
        def run_epochs(params, opt_state, batch_stats, base_key,
                       x, y, xv, yv, mask, epoch_ids):
            def body(carry, e):
                p, o, b = carry
                key = jax.random.fold_in(base_key, e)
                p, o, b, tl = epoch_one(p, o, b, x, y, key)
                m = eval_one(p, b, xv, yv, mask)
                return (p, o, b), (tl, m)

            (p, o, b), (tls, ms) = jax.lax.scan(
                body, (params, opt_state, batch_stats), epoch_ids
            )
            return p, o, b, tls, ms

        self.train_epochs = jax.jit(
            jax.vmap(
                run_epochs,
                in_axes=(0, 0, 0, 0, None, None, None, None, None, None),
            ),
            donate_argnums=(0, 1, 2),
        )

    def param_count(self, base_keys, lrs, wds) -> int:
        """Per-row parameter count via eval_shape pricing (nothing is
        allocated) — the ``params`` term of the multi-objective
        scalarization.  Constant across a population (same architecture),
        so it scales the emitted objective without changing in-population
        ranking."""
        if self._param_count is None:
            tpl = jax.eval_shape(self.init_population, base_keys, lrs, wds)
            self._param_count = sum(
                int(np.prod(leaf.shape[1:]))  # drop the population axis
                for leaf in jax.tree.leaves(tpl[0])
            )
        return self._param_count

    def pbt_generation_program(self, spec, *, interval: int, n_gens: int,
                               n_rows: int, n_valid: int, metric: str,
                               objective, log):
        """The jitted generation-scan program for one (spec, geometry).

        Cached per (scan lengths, population size, metric, mutation
        constants): chunked dispatches of the same generation count reuse
        ONE compiled program, and the canonical key rides the same
        compilecache identity space as every other driver's programs
        (interval/objective split the key; the PBT seed — per-row PRNG
        key arguments — does not)."""
        cache_key = (
            interval, n_gens, n_rows, n_valid, metric, spec["sign"],
            spec["quantile"], spec["resample_p"], spec["factors"],
            tuple(tuple(sorted(e.items())) for e in spec["specs"]),
        )
        from distributed_machine_learning_tpu.compilecache import (
            get_counters,
            pbt_program_key,
        )

        hit = self._pbt_programs.get(cache_key)
        if hit is not None:
            get_counters().add("program_hits")
            return hit
        get_counters().add("program_misses")
        from distributed_machine_learning_tpu.tune._regression_program import (
            make_pbt_generation_fn,
        )

        key_spec = {
            k: v for k, v in spec.items() if k != "keys"
        }
        key_spec["keys"] = list(spec["keys"])
        key_spec["specs"] = [dict(e) for e in spec["specs"]]
        prog_key = pbt_program_key(
            self._static_cfg,
            interval=interval,
            generations=n_gens,
            rows=n_rows,
            objective=objective,
            mutation_spec=key_spec,
            batch_shape=[
                tuple(self.data.x_train.shape), tuple(self.data.x_val.shape)
            ],
            extra={"vectorized": 1},
        )
        run = jax.jit(
            make_pbt_generation_fn(
                self._epoch_one, self._eval_one, spec,
                interval=interval, num_epochs_total=self.num_epochs,
                metric=metric, n_rows=n_rows, n_valid=n_valid,
            ),
            donate_argnums=(0, 1, 2),
        )
        log(
            f"PBT generation scan: {n_gens} generation(s) x {interval} "
            f"epoch(s) over {n_rows} rows compiled as one program "
            f"[{prog_key}]"
        )
        self._pbt_programs[cache_key] = (run, prog_key)
        return run, prog_key

    def rebind_data(self, train_data: Dataset, val_data: Dataset,
                    force: bool = False) -> None:
        """Point this (possibly cache-reused) program at fresh data.

        Every jitted program takes the data as ARGUMENTS, so a program
        traced once serves any data of the same staged shapes; only
        ``init_one``'s baked ``sample_x`` constant is from the original
        data, and flax init consumes it for shapes alone (param values
        come from the rngs).  Unchanged content (full crc32 for small
        arrays, strided sample above _FULL_HASH_BYTES — object identity
        alone would miss in-place mutation like ``train.y[:] = new``) ->
        keep the staged device buffers (no re-upload); changed, or
        ``force=True`` (run_vectorized's force_restage escape) ->
        re-stage.
        """
        sums = _data_checksums(train_data, val_data)
        if sums == self._data_sums and not force:
            return
        from distributed_machine_learning_tpu.models import compute_dtype_of

        cfg = self._static_cfg
        self.data = stage_data(
            train_data, val_data, int(cfg.get("batch_size", 32)),
            compute_dtype_of(cfg) or jnp.float32,
        )
        self._data_sums = sums
        self._data_replicated = False

    def staged_nbytes(self) -> int:
        return sum(
            int(getattr(a, "nbytes", 0))
            for a in (self.data.x_train, self.data.y_train,
                      self.data.x_val, self.data.y_val)
        )


# Cross-call program cache: repeated ``run_vectorized`` calls with the same
# static config and data shapes (bench warm repeats; users iterating on a
# sweep in one process) reuse the traced jit callables instead of paying a
# full retrace + staged re-upload per call — host seconds that land
# directly in the measured sweep wall (the duty-cycle gap vs BASELINE.md's
# >=90% target).  Single-device only: mesh identity is not part of the key.
# Entries pin their staged splits in device memory; eviction is LRU by
# count AND total staged bytes, and ``clear_program_cache`` frees it all.
_PROGRAM_CACHE: Dict[Tuple, "_GroupProgram"] = {}
_PROGRAM_CACHE_MAX = 4
_PROGRAM_CACHE_MAX_BYTES = 256 * 1024 * 1024


def clear_program_cache() -> None:
    """Drop every cached group program (frees their staged device data)."""
    _PROGRAM_CACHE.clear()


def _data_fingerprint(train_data: Dataset, val_data: Dataset) -> Tuple:
    return tuple(
        (tuple(a.shape), str(a.dtype))
        for a in (train_data.x, train_data.y, val_data.x, val_data.y)
    )


# Arrays at or below this byte size get an EXACT full-buffer fingerprint;
# larger ones a strided sample (advisor r4: a sampled checksum alone let an
# in-place edit confined to non-sampled indices reuse stale staged data).
# 64 MB covers every realistic HPO split at exact strength for ~10ms.
_FULL_HASH_BYTES = 64 * 1024 * 1024


def _data_checksums(train_data: Dataset, val_data: Dataset) -> Tuple:
    """Content fingerprint for staged-data reuse.

    Arrays <= ``_FULL_HASH_BYTES`` are hashed IN FULL (zlib.crc32 over the
    raw buffer — any in-place edit changes the fingerprint, bit-exact).
    Larger arrays fall back to a strided sample (~64k elements: crc32 +
    float64 sum), which catches realistic whole-array edits (new targets,
    rescaling, renormalization) but CAN miss an edit confined to
    non-sampled indices — documented in docs/api.md; pass
    ``force_restage=True`` (run_vectorized) or ``clear_program_cache()``
    to override."""
    import zlib

    sums = []
    for a in (train_data.x, train_data.y, val_data.x, val_data.y):
        flat = np.ascontiguousarray(np.ravel(a))
        if flat.nbytes <= _FULL_HASH_BYTES:
            sums.append((flat.size, "full", zlib.crc32(flat.view(np.uint8))))
        else:
            stride = max(1, flat.size // 65536)
            sample = np.ascontiguousarray(flat[::stride])
            sums.append((
                flat.size, "sampled", zlib.crc32(sample.view(np.uint8)),
                float(np.sum(sample, dtype=np.float64)),
            ))
    return tuple(sums)


def _group_program_for(sig: Tuple, static_cfg: Dict[str, Any],
                       train_data: Dataset, val_data: Dataset,
                       pop_sharding, device, log,
                       force_restage: bool = False) -> "_GroupProgram":
    from distributed_machine_learning_tpu.compilecache import get_counters

    if pop_sharding is not None:
        get_counters().add("program_misses")
        return _GroupProgram(static_cfg, train_data, val_data, pop_sharding)
    # Device identity is part of the key (advisor r4): on a multi-device
    # host, a run with a different explicit device= must not silently hit
    # an entry whose staged buffers and traced programs live elsewhere.
    dev_id = (getattr(device, "platform", "cpu"), getattr(device, "id", 0))
    key = (sig, _data_fingerprint(train_data, val_data), dev_id)
    prog = _PROGRAM_CACHE.pop(key, None)
    if prog is not None:
        get_counters().add("program_hits")
        prog.rebind_data(train_data, val_data, force=force_restage)
        log("program cache hit: reusing traced group program"
            + (" (forced re-stage)" if force_restage else ""))
    else:
        get_counters().add("program_misses")
        prog = _GroupProgram(static_cfg, train_data, val_data, None)
    _PROGRAM_CACHE[key] = prog  # re-insert = LRU touch (dicts are ordered)
    while len(_PROGRAM_CACHE) > 1 and (
        len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX
        or sum(p.staged_nbytes() for p in _PROGRAM_CACHE.values())
        > _PROGRAM_CACHE_MAX_BYTES
    ):
        _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
    return prog


def _stopper_epoch_fraction(sched, num_epochs: int) -> float:
    """Idealized fraction of trial-epochs a rung-based stopper computes.

    Successive-halving geometry from the scheduler's own knobs (ASHA /
    HyperBand expose ``grace_period`` and ``eta``): survivors thin by
    1/eta at each rung, so expected epochs per trial are
    sum_i survivors_i * rung_increment_i.  Schedulers without those
    knobs (median etc.) get a 0.5 prior.
    """
    g = getattr(sched, "grace_period", None)
    rf = getattr(sched, "eta", None) or getattr(sched, "reduction_factor", None)
    if not g or not rf or rf <= 1 or num_epochs <= 0:
        return 0.5
    frac_num, prev, surv, e = 0.0, 0, 1.0, float(g)
    while prev < num_epochs:
        nxt = min(e, float(num_epochs))
        frac_num += surv * (nxt - prev)
        prev, e, surv = nxt, e * rf, surv / rf
    return min(max(frac_num / num_epochs, g / num_epochs), 1.0)


def _fit_dispatch_model(obs):
    """Least-squares (latency, per-row-epoch exec) from dispatch history.

    Model: exec_s = latency + chunk * rows * ppe.  Needs two observations
    with distinct chunk*rows; returns None otherwise (or on a degenerate
    fit with negative components)."""
    if len(obs) < 2:
        return None
    x = np.array([o["chunk"] * o["rows"] for o in obs], dtype=float)
    y = np.array([o["exec_s"] for o in obs], dtype=float)
    if len(set(x.tolist())) < 2:
        return None
    a = np.stack([np.ones_like(x), x], axis=1)
    (lat, ppe), *_ = np.linalg.lstsq(a, y, rcond=None)
    if lat < 0 or ppe <= 0:
        return None
    return float(lat), float(ppe)


def _resolve_auto_dispatch(program, sched, pbt, rows_now: int, log,
                           pbt_compiled: bool = False) -> int:
    """Pick epochs_per_dispatch for this sweep from measured history.

    The trade: rung-sized chunks let a
    stopper SAVE the pruned trials' compute, but pay per-dispatch latency
    and per-new-size compiles — at latency-bound shapes a warm
    whole-budget program beats pruning (exec_speedup_vs_fifo 0.88 when
    chunked: an earlier round's figure, not measured on today's code).  Whole-budget "speculative" dispatch runs every
    trial to max_t in the one cached program and applies rung stops
    post-hoc to the per-epoch record stream — identical reported
    results (stops land at the same rungs), more row-epochs, less wall
    when dispatch latency dominates.  Boundary-mode PBT can never
    speculate (exploit mutates mid-flight state on host); COMPILED PBT
    runs whole-budget outright — its generation scan mutates that state
    in-program.  FIFO always runs whole-budget.
    """
    from distributed_machine_learning_tpu.tune.schedulers.base import (
        FIFOScheduler,
    )

    if pbt is not None:
        if pbt_compiled:
            # Exploit/explore is compiled INTO the program (generation
            # scan), so nothing forces a host round-trip per interval:
            # dispatch the whole budget at once — host dispatches for a
            # PBT sweep drop from num_epochs/interval to
            # ceil(num_epochs/chunk).
            return program.num_epochs
        # Boundary mode: one state gather per dispatch boundary, so the
        # chunk must match the perturbation cadence.
        return max(int(pbt.interval), 1)
    if isinstance(sched, FIFOScheduler):
        return program.num_epochs
    # Speculation horizon: the stopper ends every trial at max_t, and the
    # chunked loop early-exits once all rows are inactive — so both arms
    # of the comparison (and the speculative pick itself) are bounded by
    # max_t, not the config's num_epochs.
    e_total = min(
        program.num_epochs,
        int(getattr(sched, "max_t", program.num_epochs)
            or program.num_epochs),
    )
    cadence = max(int(getattr(sched, "grace_period", 1) or 1), 1)
    cadence = min(cadence, e_total)
    frac = _stopper_epoch_fraction(sched, e_total)
    obs = program.dispatch_obs
    fit = _fit_dispatch_model(obs)
    if fit is not None:
        lat, ppe = fit
        # An XLA program is keyed by BOTH the scan trip count and the
        # population row count: charge whichever arm would compile a
        # (chunk, rows) combination this program has not yet dispatched —
        # keying on chunk alone under-charged both arms whenever rows_now
        # differed from every observation (ADVICE r5).
        seen_programs = {(o["chunk"], o["rows"]) for o in obs}
        worst_compile = max((o["compile_s"] for o in obs), default=0.0)
        spec = (lat + e_total * rows_now * ppe
                + (0.0 if (e_total, rows_now) in seen_programs
                   else worst_compile))
        n_disp = -(-e_total // cadence)
        chunked = (n_disp * lat + frac * e_total * rows_now * ppe
                   + (0.0 if (cadence, rows_now) in seen_programs
                      else worst_compile))
        pick = e_total if spec <= chunked else cadence
        log(
            f"epochs_per_dispatch auto: fit latency={lat:.2f}s "
            f"per-row-epoch={ppe * rows_now:.4f}s(x{rows_now}) -> "
            f"speculative {spec:.1f}s vs chunked {chunked:.1f}s "
            f"(frac {frac:.2f}) -> {pick}"
        )
        return pick
    whole = [o for o in obs if o["chunk"] >= e_total and o["rows"]]
    if whole:
        # Cold-chunk history: only whole-budget runs observed (e.g. the
        # FIFO pass that populated the program cache).  Known: a warm
        # whole-budget pass costs ~w.  Chunking would save at most
        # (1-frac)*w but pays >=1 fresh-size compile; decide on that
        # bound.
        w = min(o["exec_s"] * rows_now / o["rows"] * e_total / o["chunk"]
                for o in whole)
        est_compile = max((o["compile_s"] for o in obs), default=0.0)
        savings = (1.0 - frac) * w
        pick = e_total if savings <= est_compile else cadence
        log(
            f"epochs_per_dispatch auto: whole-budget history only "
            f"(~{w:.1f}s exec, best-case chunk savings {savings:.1f}s vs "
            f"compile ~{est_compile:.1f}s) -> {pick}"
        )
        return pick
    return cadence


def run_vectorized(
    param_space: Union[Dict[str, Any], SearchSpace],
    *,
    train_data: Dataset,
    val_data: Dataset,
    metric: str,
    mode: str = "min",
    num_samples: int = 10,
    max_batch_trials: int = 16,
    scheduler: Optional[TrialScheduler] = None,
    search_alg: Optional[Searcher] = None,
    storage_path: str = "~/dml_tpu_results",
    name: Optional[str] = None,
    seed: int = 0,
    device=None,
    devices: Optional[List] = None,
    verbose: int = 1,
    compile_cache_dir: Optional[str] = "auto",
    compaction: str = "auto",
    epochs_per_dispatch="auto",
    pbt_mode: str = "auto",
    input_mode: str = "auto",
    checkpoint_every_epochs: int = 0,
    checkpoint_format: str = "msgpack",
    resume: bool = False,
    callbacks: Optional[List] = None,
    points_to_evaluate: Optional[List[Dict[str, Any]]] = None,
    stop=None,
    force_restage: bool = False,
    progress_deadline_s: Optional[float] = None,
    progress_grace_s: Optional[float] = None,
) -> ExperimentAnalysis:
    """Run an HPO sweep with trials batched into vmapped populations.

    Same observable contract as ``tune.run`` (per-epoch results with
    ``training_iteration``/``time_total_s``, experiment store on disk,
    ``ExperimentAnalysis`` with ``best_config``) but executed as one program
    per static-signature group per chunk.

    ``devices``: pass >1 devices (this process's — e.g.
    ``jax.local_devices()``) to shard the POPULATION AXIS over a 1-D
    ``jax.sharding.Mesh`` — trials are independent, so XLA partitions the
    vmapped program with zero cross-device communication and N chips train
    N slices of the population in parallel.  The BASELINE.md "256 concurrent
    trials on v5e-256" shape is one such sweep per pod host over its local
    chips (cross-host needs no collectives either; coordination above that
    is ``tune.cluster``'s job).  Data is replicated; population sizes are
    padded to a multiple of ``n_devices`` (x8 sublane alignment on TPU), so
    keep ``max_batch_trials >= size multiple`` or dummy pad rows dominate.
    ``device``: run on one explicit device (mutually exclusive).

    ``epochs_per_dispatch``: scan E epochs (train+eval each) inside ONE
    jitted program, cutting host->device round trips from 2E to 1 — the big
    lever when dispatch latency dominates (small models, remote TPU).  The
    per-epoch result stream is unchanged (the program returns per-epoch
    losses/metrics stacked), but scheduler stops, PBT perturbations, and
    compaction act at dispatch boundaries, so mid-chunk stops save
    reporting, not FLOPs — pick E to match the scheduler's cadence (e.g.
    ASHA's grace_period, PBT's perturbation_interval).  The default
    ``"auto"`` picks from measured dispatch history riding the cross-call
    program cache (``_resolve_auto_dispatch``): whole-budget for FIFO,
    the perturbation interval for PBT, and for rung stoppers either
    rung-sized chunks (pruning saves compute) or ONE speculative
    whole-budget dispatch reusing the cached program (stops land
    post-hoc at the same rungs; identical reported results) — whichever
    the latency/per-epoch-cost fit predicts is faster.  A user ``stop``
    rule or ``checkpoint_every_epochs`` caps the auto pick so those
    keep their dispatch-boundary semantics; pass an int to force a
    chunk size.

    ``input_mode``: accepted for surface parity with ``tune.run`` /
    ``run_distributed``.  ``"streaming"`` FALLS BACK to resident staging
    in this driver (logged + counted as ``host_input.mode_fallbacks`` in
    ``experiment_state.json``): population programs gather every row's
    shuffled batches in-program from the shared staged splits, and
    per-row permutations would multiply a host-side chunk gather (and
    its slab bytes) by the population size.  Out-of-core datasets belong
    on ``tune.run``'s per-trial executors (``data/pipeline.py``).

    ``pbt_mode``: how a ``PopulationBasedTraining`` sweep executes its
    exploit/explore.  ``"auto"`` (default) compiles the whole sweep as a
    generation scan — ranking, the state gather, and the lr/wd explore
    in-device, one host dispatch per generation chunk — whenever the
    scheduler allows it (continuous unquantized lr/wd domains, no ``stop``
    rules, not PB2), else falls back to the host-boundary path.
    ``"compiled"`` demands the in-device path (raises if impossible);
    ``"boundary"`` forces the per-interval host round-trip — useful for
    A/B debugging, and exact: both modes share one deterministic decision
    step (same threefry draws, same f32 arithmetic, grid-based
    resampling), so they produce identical exploit pairs and perturbed
    values on the same seed.  The ``experiment_state.json["pbt"]`` block
    (mode, generations, exploits, explores, host_dispatches) records
    which path actually ran.

    ``checkpoint_every_epochs``: preemption tolerance for long sweeps — at
    matching dispatch boundaries the WHOLE in-flight population (params,
    optimizer state, PRNG keys, row mapping, PBT-mutated lr/wd, and its
    trial ids) is checkpointed to ``<experiment>/population.ckpt``.
    ``resume=True`` (requires ``name``) reopens the experiment: chunks
    that finished before the interruption replay from disk into the
    scheduler/searcher, the in-flight chunk restores its device state and
    continues from the checkpointed epoch — bit-identical to an
    uninterrupted run — and sampling then continues toward
    ``num_samples``.  (Chunks spanning multiple static-signature groups
    disable the population checkpoint for that chunk; the common
    fixed-architecture sweep is single-group.)

    ``checkpoint_format``: ``"msgpack"`` keeps the legacy single-blob
    ``population.ckpt`` (overwritten in place).  ``"sharded"`` routes
    population checkpoints through a ``ckpt.CheckpointManager`` over
    ``<experiment>/population/`` — ASYNC saves (the next chunk dispatches
    while chunks/index/COMMIT land in the background), per-shard chunk
    files when the population is mesh-sharded, keep-2 retention, and
    commit-protocol crash safety: a save preempted mid-write is
    uncommitted, so ``resume`` falls back to the previous committed
    generation instead of dying on a torn file.  Resume auto-detects
    whichever format the interrupted run wrote.

    ``force_restage``: re-upload the staged data splits even when the
    content fingerprint matches a cached program's.  Only needed for
    arrays above the full-hash threshold (64 MB) edited in place at
    indices the strided sample might miss — see ``_data_checksums``.

    ``progress_deadline_s``: fail-slow detection for the dispatch loop
    (liveness.py).  A vectorized dispatch blocks this thread until the
    device syncs, so a hung backend
    is pure silence; with a deadline set, a watchdog thread flags any
    dispatch that has not synced within it — stall diagnostics (epoch
    window, rows, age) go to stderr immediately for forensics, and
    counters land in ``experiment_state.json["liveness"]``.  The
    watchdog cannot unblock the device call; it makes the hang visible
    (and the bench parent's heartbeat-staleness kill actionable) instead
    of silent.  ``progress_grace_s`` adds first-dispatch allowance
    (tracing + XLA compile; default ``max(3 * deadline, 30)``).
    """
    if mode not in ("min", "max"):
        raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    if input_mode not in hostpipe.INPUT_MODES:
        raise ValueError(
            f"input_mode must be one of {hostpipe.INPUT_MODES}, "
            f"got {input_mode!r}"
        )
    host_input_base = hostpipe.get_host_input_counters().snapshot()
    input_mode_requested = input_mode
    if input_mode == "streaming":
        # The population program gathers every row's shuffled batches
        # IN-program from the shared staged splits — per-row permutations
        # mean a host-side chunk gather would multiply host work (and slab
        # bytes) by the population size.  Streaming therefore falls back
        # to resident staging here, counted and logged; use tune.run's
        # per-trial executors for out-of-core datasets.
        hostpipe.get_host_input_counters().add("mode_fallbacks")
        input_mode = "resident"
    from distributed_machine_learning_tpu import compilecache as cc

    if compile_cache_dir is not None:
        # One sweep = one compile per static-signature group; the persistent
        # cache extends that amortization across sweeps and processes.
        cc.enable_persistent_cache(
            None if compile_cache_dir == "auto" else compile_cache_dir
        )
    tracker = cc.get_tracker()
    compile_s_at_start = tracker.total_seconds()
    compile_tracker_base = tracker.snapshot()
    compile_counters_base = cc.get_counters().snapshot()
    if compaction not in ("auto", "always", "never"):
        raise ValueError(
            f"compaction must be 'auto', 'always' or 'never', got {compaction!r}"
        )
    if device is not None and devices:
        raise ValueError("pass either device or devices, not both")
    if devices and any(
        d.process_index != jax.process_index() for d in devices
    ):
        raise ValueError(
            "run_vectorized shards the population over devices addressable "
            "by THIS process; for a multi-host pod run one run_vectorized "
            "per host over jax.local_devices() (population sharding needs "
            "no cross-host collectives), or use tune.cluster for a "
            "driver/worker topology"
        )
    space = (
        param_space if isinstance(param_space, SearchSpace)
        else SearchSpace(param_space)
    )
    stop = resolve_stop(stop)  # validate dict/callable/Stopper up front
    searcher = maybe_warm_start(search_alg or RandomSearch(), points_to_evaluate)
    searcher.set_search_space(space, seed)
    sched = scheduler or FIFOScheduler()
    from distributed_machine_learning_tpu.tune.schedulers.pbt import (
        PopulationBasedTraining,
    )

    pbt: Optional[PopulationBasedTraining] = None
    if isinstance(sched, PopulationBasedTraining):
        # Vectorized PBT: the population IS the vmapped batch, so exploit is
        # a device-side row gather (bottom-quantile rows copy top-quantile
        # rows' params + optimizer state in one program) and explore rewrites
        # the per-row lr/wd in the injected optimizer hyperparams — no
        # stop-and-respawn, no checkpoint round-trip.  Only hyperparams that
        # are optimizer STATE can mutate here; static keys change the traced
        # program and need tune.run's respawn PBT.
        bad = set(sched.mutations) - {"learning_rate", "weight_decay"}
        if bad:
            raise ValueError(
                f"vectorized PBT can only mutate learning_rate/weight_decay "
                f"(optimizer-state hyperparams); {sorted(bad)} change the "
                f"compiled program — use tune.run for those"
            )
        pbt = sched
    sched.set_experiment(metric, mode)
    # ---- PBT execution mode ------------------------------------------------
    # "compiled": the WHOLE sweep is one generation-scan program — exploit
    # ranking, the state gather, and the lr/wd explore all run in-device, and
    # the host dispatches once per generation CHUNK instead of once per
    # perturbation interval.  "boundary": the legacy host round-trip per
    # interval — required by schedulers whose explore consults host state
    # every generation (PB2's GP), by non-continuous mutation specs, and by
    # per-epoch host decisions (stop= rules).  "auto" compiles when it can.
    if pbt_mode not in ("auto", "compiled", "boundary"):
        raise ValueError(
            f"pbt_mode must be 'auto', 'compiled' or 'boundary', "
            f"got {pbt_mode!r}"
        )
    pbt_compiled = False
    pbt_spec = None
    pbt_counters: Dict[str, Any] = {}
    if pbt is not None:
        if pbt.objective_weights != (0.0, 0.0) and mode != "min":
            raise ValueError(
                "PopulationBasedTraining(objective=...) scalarizes "
                "quality x latency x params as a COST product — it is only "
                "defined for mode='min' metrics"
            )
        pbt_spec = pbt.device_mutation_spec()
        boundary_reasons = []
        if pbt_spec is None:
            boundary_reasons.append(
                "the scheduler/mutation specs need per-generation host "
                "decisions (PB2, list/quantized/callable specs)"
            )
        if stop is not None:
            boundary_reasons.append(
                "stop= rules decide per epoch on host"
            )
        if pbt_mode == "compiled" and boundary_reasons:
            raise ValueError(
                "pbt_mode='compiled' is impossible here: "
                + "; ".join(boundary_reasons)
            )
        pbt_compiled = pbt_mode != "boundary" and not boundary_reasons
        pbt_counters = {
            "generations": 0, "exploits": 0, "explores": 0,
            "host_dispatches": 0,
        }

    if resume and not name:
        raise ValueError("resume=True requires name= of the prior run")
    name = name or f"vexp_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:6]}"
    # Hand-ended: vec.run after the teardown; vec.setup by the population
    # it sets up, at its first dispatch (a later chunk opens its own, so
    # the suggest/program/init of every chunk lie in one).
    run_span = _obs.span("vec.run", {"name": name})
    setup_span = _obs.span("vec.setup")
    store = ExperimentStore(storage_path, name)
    store.set_context(metric, mode)
    start_time = time.time()
    # Observability plane: flight dumps (dispatch stalls) land in the
    # experiment root; obs counter deltas publish at teardown.
    _prev_dump_dir = _obs.dump_dir()
    _obs.configure(dump_dir=store.root)
    _obs_counters_base = _obs.get_registry().counters_snapshot()

    def log(msg: str):
        if verbose:
            print(f"[tune.vectorized] {msg}", flush=True)

    if input_mode_requested == "streaming":
        log(
            "input_mode='streaming' falls back to resident staging here: "
            "population programs gather per-row permutations in-program "
            "from the shared staged splits (counted as "
            "host_input.mode_fallbacks; use tune.run for out-of-core "
            "datasets)"
        )

    if pbt is not None:
        log(
            "PBT mode: "
            + ("compiled (exploit/explore in-program; host dispatches span "
               "generations)" if pbt_compiled
               else "boundary (host gather per perturbation interval)")
        )

    from distributed_machine_learning_tpu.tune.callbacks import (
        with_default_reporter,
    )

    callbacks = with_default_reporter(callbacks, verbose)

    def safe_cb(hook: str, *cb_args):
        from distributed_machine_learning_tpu.tune.callbacks import (
            dispatch_safely,
        )

        dispatch_safely(callbacks, hook, *cb_args, log=log)

    watchdog = None
    if progress_deadline_s is not None:
        from distributed_machine_learning_tpu.liveness import DispatchWatchdog

        def _on_dispatch_stall(event):
            # Straight to stderr, not log(): a stalled dispatch is exactly
            # the moment forensics channels matter (the bench parent reads
            # the child's stderr tail after a heartbeat-staleness kill).
            info = event.info or {}
            print(
                f"[tune.vectorized] WARNING: dispatch stalled — no device "
                f"sync in {event.age_s:.1f}s (deadline "
                f"{event.deadline_s:.1f}s): epochs "
                f"{info.get('epoch0', '?')}..{info.get('epoch_end', '?')} "
                f"over {info.get('rows', '?')} rows",
                file=sys.stderr, flush=True,
            )
            # And the flight ring: the dump shows what the driver was
            # doing in the run-up to the wedge (last dispatches, ckpt
            # submits, compile events).
            _obs.dump_flight_recorder(
                "vectorized_dispatch_stall",
                extra={"age_s": round(event.age_s, 2), **info},
            )

        # The dispatch blocks THIS thread, so detection needs the monitor
        # thread (unlike tune.run's polled watchdog).
        watchdog = DispatchWatchdog(
            progress_deadline_s, on_stall=_on_dispatch_stall,
            first_beat_grace_s=progress_grace_s,
        ).start()

    mesh = pop_sharding = repl_sharding = None
    if devices and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("pop",))
        pop_sharding = NamedSharding(mesh, P("pop"))
        repl_sharding = NamedSharding(mesh, P())
        device = devices[0]
    elif devices:
        device = devices[0]
    device = device or jax.devices()[0]
    # Population sizes stay multiples of 8 on accelerators: the sublane-
    # aligned sizes are the ones XLA:TPU tiles cleanly (empirically, this
    # backend kernel-faults on some ragged population sizes — 25/26/28 crash
    # while 8/16/24/32/40/50 run; aligned targets sidestep the fault and
    # tile better anyway).  With a mesh, sizes must also divide evenly over
    # the population axis.
    size_multiple = 1 if device.platform == "cpu" else 8
    if mesh is not None:
        size_multiple *= len(devices)
    if max_batch_trials < size_multiple:
        # A chunk smaller than the alignment multiple would be mostly dummy
        # pad rows — raise the chunk size so every padded row can carry a
        # real trial (chunks still cap at num_samples when fewer remain).
        log(
            f"max_batch_trials raised {max_batch_trials} -> {size_multiple} "
            f"to match the population size multiple "
            f"({len(devices) if mesh is not None else 1} device(s))"
        )
        max_batch_trials = size_multiple
    trials: List[Trial] = []
    programs: Dict[Tuple, _GroupProgram] = {}
    next_index = 0
    exhausted = False
    row_epochs = 0  # trial-epochs actually computed (compaction shrinks this)
    exec_total_s = 0.0  # device-execute seconds across all populations

    if checkpoint_format not in ("msgpack", "sharded"):
        raise ValueError(
            f"checkpoint_format must be 'msgpack' or 'sharded', "
            f"got {checkpoint_format!r}"
        )
    ckpt_path = (
        os.path.join(store.root, "population.ckpt")
        if checkpoint_every_epochs else None
    )
    pop_manager = None
    if checkpoint_every_epochs and checkpoint_format == "sharded":
        from distributed_machine_learning_tpu.ckpt import CheckpointManager

        # Generations under <experiment>/population/, async so the next
        # chunk dispatches while the write lands; keep-2 retention gives
        # the commit-protocol fallback a prior generation to land on.
        # Construction cleans any uncommitted debris a preempted run left.
        pop_manager = CheckpointManager(
            os.path.join(store.root, "population"),
            checkpoint_format="sharded", keep=2, async_save=True, log=log,
        )
        ckpt_path = pop_manager.directory
    from distributed_machine_learning_tpu.ckpt import get_metrics as _ckpt_m

    ckpt_metrics_base = _ckpt_m().snapshot()
    resume_state = None
    unstarted: List[Trial] = []
    if resume:
        # The checkpoint records its population's trial_ids, so a
        # multi-chunk sweep resumes too: finished chunks replay from disk,
        # the in-flight chunk restores its device state, and sampling
        # continues toward num_samples afterwards.
        resume_state, finished_trials, live_batch, unstarted = (
            _load_resume_state(store.root, metric, mode, sched,
                               searcher, pbt, stop_rules=stop)
        )
        trials = sorted(
            finished_trials + live_batch + unstarted, key=lambda t: t.trial_id
        )
        next_index = len(trials)
        searcher.fast_forward(next_index)

    def _teardown():
        """Always runs (exceptions, Ctrl-C): persist state, close the store,
        and let callbacks see experiment end (ProfilerCallback must stop the
        process-global trace; JsonlCallback must close its file) — the same
        guarantee tune.run makes."""
        wall = time.time() - start_time
        # MEASURED duty cycle: device-execute seconds (train+eval dispatch
        # to sync, compile excluded) over wall clock — not a hardcoded 1.0.
        # With a population mesh every device computes its slice
        # concurrently, so the fraction applies to all of them alike.
        utilization = (
            round(min(exec_total_s / wall, 1.0), 4) if wall > 0 else 0.0
        )
        extra = {
            "wall_clock_s": wall,
            "device_utilization": utilization,
            "device_exec_s": round(exec_total_s, 3),
            "vectorized": True,
            "row_epochs_computed": row_epochs,
            "population_sharded_over": (
                len(devices) if mesh is not None else 1
            ),
            # This RUN's compile seconds (tracker is process-wide).
            "compile_time_total_s": round(
                tracker.total_seconds() - compile_s_at_start, 3
            ),
            "compile_cache_hits": tracker.total_cache_hits(),
            "compile_cache_entries": cc.cache_entry_count(),
            # Compile counter family for THIS run: tracker event deltas
            # (uncached backend compiles, persistent-cache hits) plus the
            # group-program hit/miss counters — population programs load
            # through the same key space as every other driver.
            "compile": cc.state_block(
                compile_tracker_base, compile_counters_base
            ),
        }
        if watchdog is not None:
            watchdog.close()
            extra["liveness"] = watchdog.snapshot()
        from distributed_machine_learning_tpu import chaos as _chaos

        _plan = _chaos.active_plan()
        if _plan is not None:
            extra["injected_faults"] = _plan.snapshot()
        if pop_manager is not None:
            # Drain in-flight population writes so the directory resume
            # reads is complete (a still-queued save would otherwise be
            # silently lost with the process).
            try:
                pop_manager.close()
            except Exception as exc:  # noqa: BLE001
                log(f"population checkpoint flush failed: {exc!r}")
        ckpt_counters = _ckpt_m().delta_since(ckpt_metrics_base)
        if any(ckpt_counters.values()):
            extra["checkpoint"] = ckpt_counters
        # Host-input accounting (dataset cache activity; streaming itself
        # falls back to resident in the vectorized driver — the requested
        # mode and the fallback count are part of the record).
        hi_block = hostpipe.host_input_block(host_input_base)
        if hi_block is not None:
            hi_block["input_mode_requested"] = input_mode_requested
            extra["host_input"] = hi_block
        if pbt is not None:
            # The pbt counter family: whether a sweep actually ran
            # in-device (mode + host_dispatches) is a property of the
            # artifact, not of logs — host_dispatches >> generations /
            # (chunk/interval) is the "clamp is back" regression signal
            # (docs/performance.md counter->action table).
            extra["pbt"] = {
                "mode": "compiled" if pbt_compiled else "boundary",
                "objective": pbt.objective,
                "interval": int(pbt.interval),
                **pbt_counters,
                **pbt.debug_state(),
            }
            # The pbt family in the unified registry: the same block,
            # queryable process-wide (flight dumps embed it).
            _obs.get_registry().register_family(
                "pbt", lambda: dict(pbt_counters)
            )
        obs_delta = _obs.get_registry().delta_since(_obs_counters_base)
        obs_block = {k: v for k, v in obs_delta.items() if v}
        if obs_block:
            extra["obs"] = obs_block
        _obs.set_dump_dir(_prev_dump_dir)
        try:
            store.write_state(trials, extra=extra)
            store.close()
        except Exception as exc:  # noqa: BLE001 - callbacks still tear down
            log(f"experiment store teardown failed: {exc!r}")
        counter_scalars = {
            **{f"liveness/{k}": v
               for k, v in (extra.get("liveness") or {}).items()},
            **{f"faults/{k}": v
               for k, v in (extra.get("injected_faults") or {}).items()},
            **{f"checkpoint/{k}": v
               for k, v in (extra.get("checkpoint") or {}).items()},
            **{f"compile/{k}": v
               for k, v in (extra.get("compile") or {}).items()},
            **{f"host_input/{k}": v
               for k, v in (extra.get("host_input") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"pbt/{k}": v
               for k, v in (extra.get("pbt") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"obs/{k}": v
               for k, v in (extra.get("obs") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
        }
        if counter_scalars:
            safe_cb("on_experiment_counters", counter_scalars)
        safe_cb("on_experiment_end", trials, wall)
        return wall, utilization

    try:
        for cb in callbacks:
            cb.setup(store.root, metric, mode)
        with jax.default_device(device):
            # Chunked suggest->train loop: adaptive searchers observe all results
            # from earlier chunks before proposing the next one.
            while (
                (next_index < num_samples and not exhausted)
                or resume_state
                or unstarted
            ):
                if setup_span is None:
                    setup_span = _obs.span("vec.setup")
                if resume_state is not None:
                    chunk = list(resume_state["batch"])
                elif unstarted:
                    # Trials created but never run before the interruption
                    # (crash between their params.json writes and their
                    # chunk's first checkpoint): run them as their own chunk.
                    chunk, unstarted = list(unstarted), []
                else:
                    chunk = []
                    with _obs.span("vec.suggest") as suggest_span:
                        while (len(chunk) < max_batch_trials
                               and next_index < num_samples):
                            config = searcher.suggest(next_index)
                            if config is None:
                                exhausted = True
                                break
                            trial = Trial(
                                trial_id=f"trial_{next_index:05d}",
                                config=config,
                            )
                            next_index += 1
                            trials.append(trial)
                            chunk.append(trial)
                            sched.on_trial_add(trial)
                            store.write_params(trial)
                        suggest_span.set("trials", len(chunk))
                if not chunk:
                    break

                groups: Dict[Tuple, List[Trial]] = {}
                for t in chunk:
                    groups.setdefault(_static_signature(t.config), []).append(t)
                log(
                    f"chunk of {len(chunk)} trials in {len(groups)} static "
                    f"group(s) [{len(trials)}/{num_samples} suggested]"
                )
                group_ckpt_path = ckpt_path
                if ckpt_path and len(groups) > 1:
                    log(
                        "population checkpointing needs a single static group; "
                        f"this chunk has {len(groups)} — checkpoints disabled"
                    )
                    group_ckpt_path = None
                for sig, members in groups.items():
                    program = programs.get(sig)
                    if program is None:
                        with _obs.span("vec.program"):
                            program = programs[sig] = _group_program_for(
                                sig, dict(members[0].config), train_data,
                                val_data, pop_sharding, device, log,
                                force_restage=force_restage,
                            )
                    compile_before = tracker.thread_seconds()
                    t_pop = time.time()
                    pop_rows, pop_exec_s = _run_population(
                        program, members, sched, searcher, store, metric, mode,
                        log, tracker, compaction, size_multiple,
                        pop_sharding, repl_sharding, pbt, epochs_per_dispatch,
                        checkpoint_every_epochs, group_ckpt_path, resume_state,
                        safe_cb, stop_rules=stop, watchdog=watchdog,
                        ckpt_manager=(
                            pop_manager if group_ckpt_path else None
                        ),
                        pbt_compiled=pbt_compiled, pbt_spec=pbt_spec,
                        pbt_counters=pbt_counters, setup_span=setup_span,
                    )
                    resume_state = None  # consumed by the first (only) group
                    row_epochs += pop_rows
                    exec_total_s += pop_exec_s
                    compile_s = tracker.thread_seconds() - compile_before
                    if compile_s > 0.05:
                        log(
                            f"group of {len(members)}: "
                            f"{time.time() - t_pop - compile_s:.1f}s execute + "
                            f"{compile_s:.1f}s compile "
                            f"({tracker.thread_cache_hits()} cache hits so far)"
                        )
                setup_span = None  # ended by the chunk's first population
    finally:
        if setup_span is not None:
            setup_span.end()
        with _obs.span("vec.teardown"):
            wall, utilization = _teardown()
        run_span.end()

    analysis = ExperimentAnalysis(
        trials, metric=metric, mode=mode, root=store.root, wall_clock_s=wall,
        device_utilization=utilization,
    )
    log(
        f"experiment {name}: {analysis.num_terminated()}/{len(trials)} trials in "
        f"{wall:.1f}s ({analysis.trials_per_hour():.1f} trials/hour, "
        f"{100 * utilization:.0f}% measured device duty cycle, vectorized)"
    )
    return analysis


def _load_resume_state(
    root: str,
    metric: str,
    mode: str,
    sched: TrialScheduler,
    searcher: Searcher,
    pbt,
    stop_rules=None,
) -> Tuple[Dict[str, Any], List[Trial], List[Trial]]:
    """Rehydrate an interrupted sweep: load the population checkpoint,
    rebuild Trial objects from the on-disk store, and replay their
    per-epoch records through the scheduler/searcher so rung/model state
    matches the moment of interruption.

    Multi-chunk sweeps work too: the checkpoint's ``trial_ids`` name the
    in-flight chunk; other stored trials with records belong to chunks
    that already finished and replay as TERMINATED (no device state
    needed); record-less ones were created but never started (a crash in
    the window between a chunk's params.json writes and its
    start-of-chunk checkpoint) and re-run from scratch. Returns
    ``(resume_state, finished_trials, live_batch, unstarted)``."""
    from distributed_machine_learning_tpu import ckpt as ckpt_pkg
    from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib

    # Format auto-detect: a sharded run left generations under
    # <root>/population/ (clean torn saves first — no writer is live at
    # resume — then restore the newest COMMITTED generation, falling back
    # to older ones on damage); otherwise the legacy single-blob file.
    ck = None
    pop_dir = os.path.join(root, "population")
    if ckpt_pkg.list_generations(pop_dir):
        ckpt_pkg.cleanup_uncommitted(pop_dir)
        newest, _ = ckpt_pkg.latest_generation(pop_dir)
        if newest is not None:
            ck, _used, _step = ckpt_pkg.restore_with_fallback(
                newest, pop_dir
            )
    if ck is None:
        ck = ckpt_lib.load_checkpoint(os.path.join(root, "population.ckpt"))
    if ck is None:
        raise ValueError(
            f"resume=True but no population checkpoint under {root} "
            f"(neither population/gen_* nor population.ckpt; was the run "
            f"started with checkpoint_every_epochs > 0?)"
        )
    prior = ExperimentAnalysis.from_directory(root, metric, mode)
    all_trials = sorted(prior.trials, key=lambda t: t.trial_id)
    if not all_trials:
        raise ValueError(f"no trials found under {root}")
    active = [bool(a) for a in np.asarray(ck["active"])]
    lrs = np.asarray(ck["lrs"], np.float32)
    wds = np.asarray(ck["wds"], np.float32)
    epoch0 = int(ck["epoch0"])
    raw_ids = ck.get("trial_ids")
    if raw_ids is None:
        ck_ids = None
    elif isinstance(raw_ids, dict):
        # flax msgpack round-trips python lists as index-keyed state dicts.
        ck_ids = [str(raw_ids[k]) for k in sorted(raw_ids, key=int)]
    else:
        ck_ids = [str(i) for i in raw_ids]
    unstarted: List[Trial] = []
    if ck_ids is None:
        # Checkpoint from before trial_ids were recorded: single-chunk only.
        batch, finished = all_trials, []
    else:
        by_id = {t.trial_id: t for t in all_trials}
        missing = [i for i in ck_ids if i not in by_id]
        if missing:
            raise ValueError(
                f"population checkpoint names trials missing from {root}: "
                f"{missing}"
            )
        batch = [by_id[i] for i in ck_ids]
        others = [t for t in all_trials if t.trial_id not in set(ck_ids)]
        finished = [t for t in others if t.results]
        unstarted = [t for t in others if not t.results]
        for trial in unstarted:
            trial.config = dict(trial.config)
            sched.on_trial_add(trial)
    if len(batch) != len(active):
        raise ValueError(
            f"checkpoint population size ({len(active)}) does not match its "
            f"{len(batch)} trials under {root}"
        )
    now = time.time()

    # Chunks that finished before the interruption: full replay, terminal.
    for trial in finished:
        trial.config = dict(trial.config)
        sched.on_trial_add(trial)
        last = trial.results[-1]
        trial.started_at = now - float(last.get("time_total_s", 0.0))
        trial.reports_since_restart = len(trial.results)
        trial.status = TrialStatus.TERMINATED
        trial.finished_at = trial.started_at + float(
            last.get("time_total_s", 0.0)
        )
    _replay_records(finished, sched, searcher, pbt, metric, mode,
                    stop_rules)
    for trial in finished:
        sched.on_trial_complete(trial)
        searcher.on_trial_complete(
            trial.trial_id, trial.config, trial.last_result, metric, mode
        )
    for trial in batch:
        # The crash may have landed mid-epoch: some trials carry records
        # BEYOND the checkpoint. Those epochs re-run on resume, so drop the
        # stale records (memory and file) or they would double-count.
        kept = [
            r for r in trial.results
            if int(r.get("training_iteration", 0)) <= epoch0
        ]
        if len(kept) != len(trial.results):
            trial.results = kept
            with open(
                os.path.join(root, trial.trial_id, "result.jsonl"), "w"
            ) as f:
                for r in kept:
                    f.write(json.dumps(r) + "\n")
    for idx, trial in enumerate(batch):
        trial.config = dict(trial.config)
        # PBT may have mutated lr/wd since params.json was written.
        trial.config["learning_rate"] = float(lrs[idx])
        if "weight_decay" in trial.config:
            trial.config["weight_decay"] = float(wds[idx])
        sched.on_trial_add(trial)
        # Keep time_total_s continuous across the interruption.
        last = trial.results[-1] if trial.results else None
        trial.started_at = now - float(last["time_total_s"]) if last else now
        trial.reports_since_restart = len(trial.results)
        trial.status = (
            TrialStatus.RUNNING if active[idx] else TrialStatus.TERMINATED
        )
        if not active[idx]:
            # Freeze the stopped trial's clock at its recorded runtime, or
            # runtime_s() keeps growing for the resumed run's duration.
            trial.finished_at = trial.started_at + (
                float(last["time_total_s"]) if last else 0.0
            )
    _replay_records(batch, sched, searcher, pbt, metric, mode,
                    stop_rules)
    for idx, trial in enumerate(batch):
        if not active[idx]:
            sched.on_trial_complete(trial)
            searcher.on_trial_complete(
                trial.trial_id, trial.config, trial.last_result, metric, mode
            )
    resume_state = {
        "state_dict": ck["state"],
        "key_data": np.asarray(ck["key_data"]),
        "rows": [int(r) for r in np.asarray(ck["rows"])],
        "active": active,
        "lrs": lrs,
        "wds": wds,
        "epoch0": int(ck["epoch0"]),
        # Which PRNG impl produced key_data ("" = jax default); absent in
        # legacy checkpoints (pre-auto-resolution).
        "rng_impl": ck.get("rng_impl"),
        "batch": batch,
    }
    return resume_state, finished, batch, unstarted


def _replay_records(trial_list, sched, searcher, pbt, metric, mode,
                    stop_rules=None):
    """Route stored per-epoch records back through the scheduler/searcher in
    epoch-major order — the order the live loop produced them. (Vectorized
    PBT skips the scheduler: exploit/explore state is device-side.)
    Stateful stoppers (plateau windows) are warmed too, decisions ignored
    — a resumed sweep must stop trials at the same point a fresh one
    would."""
    max_len = max((len(t.results) for t in trial_list), default=0)
    for e in range(max_len):
        for trial in trial_list:
            if e < len(trial.results):
                record = trial.results[e]
                if pbt is None:
                    sched.on_trial_result(trial, record)
                searcher.on_trial_result(
                    trial.trial_id, dict(trial.config), record, metric, mode
                )
                if callable(stop_rules):
                    stop_hit(stop_rules, trial.trial_id, record)
    if pbt is not None:
        # Re-baseline the model-based explore (PB2) on each trial's LAST
        # record only: replaying full histories would attribute every old
        # delta to the trial's FINAL (possibly exploit-mutated) config.
        # Deltas resume from the first post-restore report; observations
        # from before the interruption are accepted as lost.
        for trial in trial_list:
            if trial.results:
                pbt.observe_result(trial, trial.results[-1])


def _emit_epoch_records(
    batch, rows, active, lrs, epoch, step_count, shape_val, now,
    train_losses, metrics_np, pbt_notes, pbt, sched, searcher, store,
    metric, mode, safe_cb=lambda *a: None, stop_rules=None, cost=None,
):
    """Append one epoch's records for every live trial and route them through
    the scheduler/searcher (the vectorized analogue of ``session.report``).

    ``cost`` (:func:`_new_emit_cost`, summed into): ``results`` and
    ``stopped`` counts and the seconds spent in the store, the callbacks,
    the scheduler and the searcher — what the caller's ``vec.emit`` span
    carries as attrs, in place of a span a result."""
    if cost is None:
        cost = _new_emit_cost()
    clock = time.perf_counter
    for i, r in enumerate(rows):
        if r < 0:  # dummy pad row
            continue
        trial = batch[r]
        if not active[r]:
            continue
        record = {
            "epoch": epoch,
            "training_iteration": epoch + 1,
            "train_loss": float(train_losses[i]),
            "steps": step_count,
            "lr": float(lrs[r]) * shape_val,
            "trial_id": trial.trial_id,
            "timestamp": now,
            "time_total_s": now - trial.started_at,
            "population_size": len(rows),
            **{key: float(v[i]) for key, v in metrics_np.items()},
        }
        note = pbt_notes.pop(r, None)
        if note is not None:
            record["pbt_exploited_from"] = note
        trial.results.append(record)
        # Keep Trial.training_iteration live (== epochs completed), the
        # same contract the threaded executor maintains via report().
        trial.reports_since_restart += 1
        cost["results"] += 1
        t0 = clock()
        store.append_result(trial, record)
        t1 = clock()
        safe_cb("on_trial_result", trial, record)
        t2 = clock()
        # PBT never stops trials and its REQUEUE protocol is replaced by
        # the in-population gather at the dispatch boundary, so the
        # scheduler's DECISION surface is bypassed — but model-based
        # explores (PB2) still learn from every report.
        if pbt is not None:
            pbt.observe_result(trial, record)
            decision = CONTINUE
        else:
            decision = sched.on_trial_result(trial, record)
        t3 = clock()
        searcher.on_trial_result(
            trial.trial_id, dict(trial.config), record, metric, mode
        )
        t4 = clock()
        cost["store_s"] += t1 - t0
        cost["callbacks_s"] += t2 - t1
        cost["scheduler_s"] += t3 - t2
        cost["searcher_s"] += t4 - t3
        if decision == REQUEUE:
            raise ValueError(
                "requeue schedulers are not supported in vectorized mode; "
                "use tune.run"
            )
        if decision == CONTINUE and stop_rules is not None:
            # Same stop surface as tune.run — one shared dispatch
            # (stoppers.stop_hit) so the drivers cannot diverge.
            if stop_hit(stop_rules, trial.trial_id, record):
                decision = STOP
        if decision == STOP:
            cost["stopped"] += 1
            active[r] = False
            trial.status = TrialStatus.TERMINATED
            trial.finished_at = time.time()
            t0 = clock()
            sched.on_trial_complete(trial)
            t1 = clock()
            searcher.on_trial_complete(
                trial.trial_id, trial.config, trial.last_result, metric, mode
            )
            t2 = clock()
            safe_cb("on_trial_complete", trial)
            cost["scheduler_s"] += t1 - t0
            cost["searcher_s"] += t2 - t1
            cost["callbacks_s"] += clock() - t2


def _new_emit_cost() -> Dict[str, float]:
    return {"results": 0, "stopped": 0, "store_s": 0.0, "callbacks_s": 0.0,
            "scheduler_s": 0.0, "searcher_s": 0.0}


def _close_emit_span(span, cost: Dict[str, float]) -> None:
    """A ``vec.emit`` span's attrs: what its results cost, by layer."""
    for key, value in cost.items():
        span.set(key, round(value, 6) if key.endswith("_s") else value)


def _pbt_objective_scale(pbt, program, base_keys, row_lr, row_wd) -> float:
    """The constant scalarization factor of the multi-objective score:
    ``step_latency_s ** lat_w * param_millions ** param_w``.

    Latency comes from the program's measured dispatch history (riding the
    cross-call program cache, so a warm sweep prices itself from the prior
    sweep's measurement; neutral 1.0 before any measurement exists) and
    params from eval_shape pricing — both constant across a population's
    rows, so in-population exploit ranking is unchanged while the emitted
    ``pbt_objective`` metric makes rows comparable ACROSS architecture
    groups (the best *deployable* model wins a multi-group sweep).  Frozen
    per population: re-reading the latency EWMA between generations would
    break the compiled-vs-boundary decision parity.
    """
    lat_w, par_w = pbt.objective_weights
    if lat_w == 0.0 and par_w == 0.0:
        return 1.0
    scale = 1.0
    if lat_w:
        obs = [o for o in program.dispatch_obs
               if o.get("exec_s") and o.get("chunk")]
        if obs:
            o = obs[-1]
            step_s = o["exec_s"] / max(o["chunk"] * program.steps_per_epoch,
                                       1)
            scale *= step_s ** lat_w
    if par_w:
        millions = program.param_count(base_keys, row_lr, row_wd) / 1e6
        scale *= millions ** par_w
    # float32: the device multiplies scores by this as an f32 scalar and
    # the host reference must see the same bits.
    return float(np.float32(scale))


def _inject_objective(pbt, obj_scale, train_losses, metrics_np):
    """Attach the scalarized objective as a per-epoch record metric
    (``pbt_objective``) when multi-objective ranking is on — pass
    ``run_vectorized(metric="pbt_objective")`` (with the quality metric
    named on the scheduler) to make best-trial selection deployability-
    aware across groups."""
    if pbt is None or pbt.objective_weights == (0.0, 0.0):
        return metrics_np
    col = (train_losses if pbt.metric == "train_loss"
           else metrics_np.get(pbt.metric))
    if col is None:
        return metrics_np
    out = dict(metrics_np)
    out["pbt_objective"] = np.asarray(col, np.float32) * np.float32(obj_scale)
    return out


def _apply_reference_exploits(batch, rows, lrs, wds, pbt, pbt_notes,
                              src, new_lr, new_wd, exploited, mut_keys):
    """Mirror one generation's (in-device or reference) exploit decisions
    into the host bookkeeping: trial configs adopt the donor's config with
    the perturbed hyperparams (the lagger keeps its own seed/identity),
    improvement chains reset, and the donor note annotates the next
    record.  Returns the (lagger, donor) trial-id pairs."""
    pairs = []
    for i in np.flatnonzero(np.asarray(exploited)):
        r = rows[int(i)]
        if r < 0:  # dummy pad rows are never laggers (ranked invalid)
            continue
        donor_r = rows[int(src[int(i)])]
        lagger, donor = batch[r], batch[donor_r]
        new_cfg = dict(donor.config)
        new_cfg["learning_rate"] = float(new_lr[int(i)])
        if "weight_decay" in new_cfg or "weight_decay" in mut_keys:
            new_cfg["weight_decay"] = float(new_wd[int(i)])
        new_cfg["seed"] = lagger.config.get("seed", 0)
        lagger.config = new_cfg
        # The laggard's weights are about to be (were) replaced by the
        # donor's: a score delta across that boundary would credit the new
        # config with the donor's head start.
        pbt.reset_improvement_chain(lagger.trial_id)
        lrs[r] = float(new_lr[int(i)])
        wds[r] = float(new_wd[int(i)])
        pbt_notes[r] = donor.trial_id
        pairs.append((lagger.trial_id, donor.trial_id))
        pbt._num_perturbations += 1
    return pairs


def _progress_note(msg: str) -> None:
    """Stderr heartbeat, on when ``DML_TUNE_PROGRESS`` is set (bench
    children set it). jit work is silent from the host side — without
    these boundary notes a run that dies at its timeout cannot tell
    WHICH phase (trace, compile or execute) hung.

    When ``DML_BENCH_HEARTBEAT_PATH`` is set (bench suite children), every
    dispatch boundary also refreshes that file's mtime: the bench parent
    kills a child on heartbeat staleness, and a chunked sweep making real
    per-epoch progress must register as alive between its phase notes."""
    touch_heartbeat()
    if (os.environ.get("DML_TUNE_PROGRESS") or "0") != "0":
        print(f"[tune.progress +{time.monotonic() - _PROGRESS_T0:.1f}s] {msg}",
              file=sys.stderr, flush=True)


_PROGRESS_T0 = time.monotonic()


def _run_population(
    program: _GroupProgram,
    batch: List[Trial],
    sched: TrialScheduler,
    searcher: Searcher,
    store: ExperimentStore,
    metric: str,
    mode: str,
    log,
    tracker,
    compaction: str = "auto",
    size_multiple: int = 1,
    pop_sharding=None,
    repl_sharding=None,
    pbt=None,
    epochs_per_dispatch: int = 1,
    ckpt_every: int = 0,
    ckpt_path: Optional[str] = None,
    resume_state: Optional[Dict[str, Any]] = None,
    safe_cb=lambda *a: None,
    stop_rules=None,
    watchdog=None,
    ckpt_manager=None,
    pbt_compiled: bool = False,
    pbt_spec=None,
    pbt_counters=None,
    setup_span=None,
) -> Tuple[int, float]:
    """Train one population of K same-shape trials to completion.

    Returns ``(row_epochs, exec_seconds)``: trial-epochs actually computed
    (rows x epochs — the honest FLOP-cost denominator under compaction) and
    device-execute wall seconds (the utilization numerator).

    ``setup_span``: the caller's open ``vec.setup`` span, ended here at the
    population's first dispatch."""
    k = len(batch)
    from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib

    # Population init (or restore), placement, dispatch sizing: ended with
    # ``setup_span`` where the dispatch loop starts.
    init_span = _obs.span("vec.init", {"trials": k})

    now = time.time()
    epoch_start = 0
    if resume_state is not None:
        # Restore the interrupted population: rebuild a template at the
        # checkpointed row count (compaction may have shrunk it), then pour
        # the saved state into it.
        lrs = np.asarray(resume_state["lrs"], np.float32)
        wds = np.asarray(resume_state["wds"], np.float32)
        rows = list(resume_state["rows"])
        active = list(resume_state["active"])
        epoch_start = int(resume_state["epoch0"])
        # Re-wrap with the impl that PRODUCED the key data: rbg keys are
        # wider than threefry's, so wrapping under the wrong impl fails
        # (or, worse, silently changes streams).  The checkpoint records
        # it ("" = jax default); legacy checkpoints predate auto-resolution
        # and used the raw config value, so fall back to exactly that —
        # resolving anew could differ if the backend changed across resume.
        saved_impl = resume_state.get("rng_impl")
        if saved_impl is not None:
            rng_impl = saved_impl or None
        else:
            rng_impl = batch[0].config.get("rng_impl") or None
        base_keys = jax.random.wrap_key_data(
            jnp.asarray(resume_state["key_data"]),
            impl=rng_impl,
        )
        row_lr = jnp.asarray(
            [lrs[r] if r >= 0 else float(lrs[0]) for r in rows], jnp.float32
        )
        row_wd = jnp.asarray(
            [wds[r] if r >= 0 else float(wds[0]) for r in rows], jnp.float32
        )
        # eval_shape: the template only provides structure/dtypes for the
        # msgpack restore — no compile, no device allocation of a population
        # that the next line would throw away.
        template = jax.eval_shape(
            program.init_population, base_keys, row_lr, row_wd
        )
        restored = ckpt_lib.restore_into(
            {"params": template[0], "opt_state": template[1],
             "batch_stats": template[2]},
            resume_state["state_dict"],
        )
        params = restored["params"]
        opt_state = restored["opt_state"]
        batch_stats = restored["batch_stats"]
        log(
            f"resumed population of {len(rows)} rows at epoch {epoch_start}"
        )
    else:
        for t in batch:
            t.status = TrialStatus.RUNNING
            t.started_at = now
            safe_cb("on_trial_start", t)

        seeds = np.asarray(
            [int(t.config.get("seed", 0)) for t in batch], np.uint32
        )
        lrs = np.asarray(
            [float(t.config["learning_rate"]) for t in batch], np.float32
        )
        wds = np.asarray(
            [float(t.config.get("weight_decay", 0.0)) for t in batch],
            np.float32,
        )
        # Pad the population up to the platform's size multiple with dummy
        # rows (row 0's hyperparams, distinct seeds).  On TPU the sublane
        # padding makes these rows nearly free, and aligned sizes avoid the
        # backend's ragged-size kernel fault (see run_vectorized).
        pad_rows = (-k) % size_multiple
        if pad_rows:
            if pad_rows >= k:
                log(
                    f"population of {k} padded to {k + pad_rows} for size "
                    f"alignment — most rows are dummies; use chunks of at "
                    f"least {size_multiple} trials to avoid the waste"
                )
            seeds = np.concatenate([seeds, seeds[:1] + 1 + np.arange(
                pad_rows, dtype=np.uint32) * 7919])
            lrs = np.concatenate([lrs, np.repeat(lrs[:1], pad_rows)])
            wds = np.concatenate([wds, np.repeat(wds[:1], pad_rows)])
        # rng_impl (static; part of the group signature via the static
        # config): resolves to the hardware RNG on TPU by default — worth
        # ~1.5x measured sweep throughput over threefry there (ops/rng.py)
        # — and is recorded in the population checkpoint so a resume
        # re-wraps key data under the impl that produced it.
        rng_impl = resolve_rng_impl(batch[0].config)
        base_keys = jax.vmap(
            lambda s: jax.random.key(s, impl=rng_impl)
        )(jnp.asarray(seeds))
        _progress_note(
            f"init_population rows={len(seeds)} (trace+compile on first use)"
        )
        params, opt_state, batch_stats = program.init_population(
            base_keys, jnp.asarray(lrs), jnp.asarray(wds)
        )
        _progress_note("init_population returned")
        active = [True] * k
        # ``rows[i]`` = index into ``batch`` of the trial living at
        # population row i (-1 for dummy pad rows, which are never
        # reported).  Compaction slices stopped rows out of the pytrees and
        # shrinks this mapping; everything per-trial (keys, lr/wd, records)
        # is looked up through it.
        rows = list(range(k)) + [-1] * pad_rows
    if pop_sharding is not None:
        # init_population already materialized params/opt_state sharded over
        # the mesh (out_shardings); keys are tiny, so placing them too just
        # saves XLA a reshard in the first epoch.  A restored state came
        # back as host arrays, so it needs placing too.
        base_keys = jax.device_put(base_keys, pop_sharding)
        if resume_state is not None:
            params, opt_state, batch_stats = jax.device_put(
                (params, opt_state, batch_stats), pop_sharding
            )
        if not getattr(program, "_data_replicated", False):
            d = program.data
            for field in ("x_train", "y_train", "x_val", "y_val", "val_mask"):
                setattr(d, field, jax.device_put(getattr(d, field),
                                                 repl_sharding))
            program._data_replicated = True

    ckpt_seq = [ckpt_manager.latest()[1] if ckpt_manager is not None else 0]

    def save_population(at_epoch: int):
        with _obs.span("vec.checkpoint", {"epoch": at_epoch}):
            _save_population(at_epoch)

    def _save_population(at_epoch: int):
        tree = {
            "state": {
                "params": params,
                "opt_state": opt_state,
                "batch_stats": batch_stats,
            },
            "key_data": np.asarray(jax.random.key_data(base_keys)),
            # Impl the key data was created under ("" = jax default);
            # resume must re-wrap with the same one (see restore above).
            "rng_impl": rng_impl or "",
            "rows": np.asarray(rows, np.int64),
            "active": np.asarray(active, np.bool_),
            "lrs": np.asarray(lrs, np.float32),
            "wds": np.asarray(wds, np.float32),
            "epoch0": at_epoch,
            # Which trials form THIS population — lets resume tell the
            # in-flight chunk apart from chunks that already finished
            # (multi-chunk sweeps overwrite this file chunk by chunk).
            "trial_ids": [t.trial_id for t in batch],
        }
        if ckpt_manager is not None:
            # Async sharded generation: the snapshot happens here (per
            # shard, so a mesh-sharded population never gathers), the
            # chunk/index/COMMIT writes land in the background while the
            # next chunk dispatches.  A preempted write stays uncommitted
            # and resume falls back to the previous committed generation.
            ckpt_seq[0] += 1
            ckpt_manager.save(ckpt_seq[0], tree)
        else:
            ckpt_lib.save_checkpoint(ckpt_path, tree)
        log(f"population checkpoint at epoch {at_epoch}")

    if ckpt_every and ckpt_path and resume_state is None:
        # Start-of-chunk checkpoint: from this moment the file on disk names
        # the chunk that is actually running. Without it, a crash before
        # this chunk's first periodic checkpoint would leave the PREVIOUS
        # chunk's stale checkpoint in place and resume would misclassify
        # this chunk's trials as finished (or unresumable).
        save_population(0)

    data = program.data
    pbt_notes: Dict[int, str] = {}  # trial index -> donor id, for the record
    row_epochs = 0
    exec_total_s = 0.0  # device-execute seconds (utilization numerator)
    exec_ema = None  # measured per-epoch execute seconds at the current size
    compile_cost_s = None  # most recent substantial compile observed
    # Speculation horizon (matches _resolve_auto_dispatch): the largest
    # chunk the auto cost model ever proposes for a rung stopper.
    e_spec = min(
        program.num_epochs,
        int(getattr(sched, "max_t", program.num_epochs)
            or program.num_epochs),
    )
    pbt_counters = pbt_counters if pbt_counters is not None else {}
    if pbt_compiled and epoch_start % max(int(pbt.interval), 1):
        # A resumed population whose checkpoint landed off a generation
        # boundary cannot re-enter the generation scan mid-generation; the
        # boundary path makes the SAME decisions (shared reference step),
        # just one dispatch per interval.
        log(
            f"PBT falling back to boundary mode: resume epoch "
            f"{epoch_start} is not a multiple of the perturbation "
            f"interval {pbt.interval}"
        )
        pbt_compiled = False
        # Overrides the driver-level mode in the teardown block (dict-merge
        # order): the artifact must say what actually ran.
        pbt_counters["mode"] = "boundary"
        pbt_counters["mode_fallbacks"] = (
            pbt_counters.get("mode_fallbacks", 0) + 1
        )
    speculative = False
    if epochs_per_dispatch == "auto":
        dispatch = _resolve_auto_dispatch(program, sched, pbt, len(rows), log,
                                          pbt_compiled=pbt_compiled)
        if stop_rules is not None:
            # User stop rules act at dispatch boundaries; a whole-budget
            # dispatch would turn a mid-sweep stop (plateau, timeout)
            # into a no-op.  Fall back to the stopper cadence.
            dispatch = min(
                dispatch,
                max(int(getattr(sched, "grace_period", 1) or 1), 1),
            )
        if ckpt_every and ckpt_path:
            # Population checkpoints land at dispatch boundaries; keep
            # the requested preemption granularity (ckpt_path None means
            # checkpointing is disabled for this chunk — no granularity
            # to preserve).
            dispatch = min(dispatch, max(int(ckpt_every), 1))
        dispatch = max(int(dispatch), 1)
        # Speculative only if the pick SURVIVED the clamps above: a
        # stop-rule or checkpoint cadence that shrank it turns the run
        # back into ordinary chunking.
        speculative = pbt is None and dispatch == e_spec
    else:
        dispatch = max(int(epochs_per_dispatch), 1)
    chunk_gens = 0
    if pbt is not None and pbt_compiled:
        # In-device generations made the old interval clamp obsolete: the
        # generation scan fires EVERY perturbation in-program, so the
        # dispatch chunk may span many intervals.  It must still be a
        # whole number of generations (round down; at least one) — the
        # per-epoch leftover below the interval runs as a trailing plain
        # chunk with no perturbation after it, same as the boundary
        # path's final partial interval.
        iv = max(int(pbt.interval), 1)
        chunk_gens = dispatch // iv
        if chunk_gens < 1:
            # A checkpoint cadence (or explicit chunk) below the interval
            # cannot fit one generation in-program: boundary fallback.
            log(
                f"PBT falling back to boundary mode: dispatch chunk "
                f"{dispatch} < perturbation interval {iv}"
            )
            pbt_compiled = False
            pbt_counters["mode"] = "boundary"
            pbt_counters["mode_fallbacks"] = (
                pbt_counters.get("mode_fallbacks", 0) + 1
            )
        elif chunk_gens * iv != dispatch:
            log(
                f"epochs_per_dispatch rounded {dispatch} -> "
                f"{chunk_gens * iv} (whole generations of {iv} epochs; "
                f"compiled PBT dispatches in generation units)"
            )
            dispatch = chunk_gens * iv
    if pbt is not None and not pbt_compiled and dispatch > pbt.interval:
        # Boundary fallback: one state gather can happen per dispatch
        # boundary, so a chunk larger than the perturbation interval would
        # silently DROP perturbations, not delay them.  Clamp so every
        # interval fires.  (The compiled path above has no such limit —
        # keeping this clamp active there is the regression the
        # host_dispatches counter exists to catch.)
        log(
            f"epochs_per_dispatch clamped {dispatch} -> {pbt.interval} to "
            f"match the PBT perturbation interval (boundary mode)"
        )
        dispatch = pbt.interval
    epoch_budget = program.num_epochs
    if dispatch > 1 and not pbt_compiled and program.num_epochs % dispatch:
        if speculative:
            # The auto resolver picked ONE whole-horizon speculative
            # dispatch (dispatch == max_t < num_epochs, not dividing it).
            # Divisor-rounding here would silently shrink the chunk to a
            # size that was never an arm of the cost comparison — and pay
            # the fresh-size compile the model predicted avoiding (ADVICE
            # r5).  Cap the epoch loop at the horizon instead: the stopper
            # ends every trial there anyway, so no ragged second chunk
            # ever dispatches.
            epoch_budget = dispatch
            log(
                f"epochs_per_dispatch speculative: epoch loop capped at "
                f"{dispatch} (scheduler horizon; num_epochs="
                f"{program.num_epochs} never dispatches past it)"
            )
        else:
            # A ragged final chunk is a second full XLA program (different
            # scan trip count) — in the dispatch-latency regime this
            # feature targets, that compile can cost more than the round
            # trips saved.  Round down to the largest divisor of
            # num_epochs so every chunk shares one compiled program.
            d = dispatch
            while program.num_epochs % d:
                d -= 1
            log(
                f"epochs_per_dispatch rounded {dispatch} -> {d} "
                f"(largest divisor of num_epochs={program.num_epochs}; "
                f"avoids a second compile for a ragged final chunk)"
            )
            dispatch = d

    # PBT deterministic-step state (compiled AND boundary-reference paths):
    # the per-ROW lr/wd the decision step last produced (float32 — the
    # exact bits the device carries in the injected optimizer state), the
    # frozen objective scalarization factor, and — compiled only — the
    # per-row PBT PRNG keys that travel with their rows.
    pbt_row_lr = pbt_row_wd = None
    obj_scale = 1.0
    pbt_keys = None
    mut_keys: Tuple[str, ...] = ()
    if pbt is not None and pbt_spec is not None:
        mut_keys = tuple(pbt_spec["keys"])
        pbt_row_lr = np.asarray(
            [lrs[r] if r >= 0 else float(lrs[0]) for r in rows], np.float32
        )
        pbt_row_wd = np.asarray(
            [wds[r] if r >= 0 else float(wds[0]) for r in rows], np.float32
        )
        obj_scale = _pbt_objective_scale(
            pbt, program, base_keys,
            jnp.asarray(pbt_row_lr), jnp.asarray(pbt_row_wd),
        )
        if obj_scale != 1.0:
            log(
                f"PBT multi-objective ranking: scores scaled by "
                f"{obj_scale:.3g} ({pbt.objective})"
            )
    if pbt_compiled:
        n_live = sum(1 for r in rows if r >= 0)
        if any(r < 0 for r in rows[:n_live]):
            # The compiled step ranks the valid PREFIX; pads are appended
            # at creation so this never trips — defensive fallback only.
            log("PBT falling back to boundary mode: non-suffix pad rows")
            pbt_compiled = False
            pbt_counters["mode"] = "boundary"
            pbt_counters["mode_fallbacks"] = (
                pbt_counters.get("mode_fallbacks", 0) + 1
            )
        else:
            _pbt_base_key = jax.random.key(int(pbt.seed))
            pbt_keys = jax.vmap(
                lambda i: jax.random.fold_in(_pbt_base_key, i)
            )(jnp.arange(len(rows)))
            if pop_sharding is not None:
                pbt_keys = jax.device_put(pbt_keys, pop_sharding)
    epoch0 = epoch_start
    # First dispatch of a population size traces + compiles; the watchdog
    # grants it the first-beat grace.  Compaction changes the compiled size,
    # so the dispatch after it is cold again.
    cold_dispatch = True
    init_span.end()
    if setup_span is not None:
        setup_span.end()
    while epoch0 < epoch_budget:
        iv = max(int(pbt.interval), 1) if pbt is not None else 1
        if (
            pbt_compiled
            and epoch0 % iv == 0
            and (epoch_budget - epoch0) >= iv
        ):
            # ---- compiled PBT: the generation scan IS the dispatch ------
            # One host round trip covers g generations: g x interval
            # epochs, g in-program rankings, g exploit gathers, g explore
            # perturbations.  Stacked per-generation outputs reconstruct
            # the full record/note stream below.
            g = min(chunk_gens, (epoch_budget - epoch0) // iv)
            gen0 = epoch0 // iv
            n_valid = sum(1 for r in rows if r >= 0)
            run, _prog_key = program.pbt_generation_program(
                pbt_spec, interval=iv, n_gens=g, n_rows=len(rows),
                n_valid=n_valid, metric=pbt.metric, objective=pbt.objective,
                log=log,
            )
            _progress_note(
                f"dispatch PBT generations {gen0}..{gen0 + g} "
                f"({g * iv} epochs) over {len(rows)} rows (first dispatch "
                f"of a shape traces+compiles)"
            )
            c0 = tracker.thread_seconds()
            t0 = time.time()
            if watchdog is not None:
                watchdog.track(
                    "dispatch",
                    info={"epoch0": epoch0, "epoch_end": epoch0 + g * iv,
                          "rows": len(rows)},
                    first_beat_grace_s=None if cold_dispatch else 0.0,
                )
            from distributed_machine_learning_tpu import chaos as _chaos

            _plan = _chaos.active_plan()
            if _plan is not None:
                _plan.maybe_hang_dispatch("vectorized", epoch0 + 1)
            data = program.data
            with _obs.span(
                "pbt.generation",
                {"gen0": gen0, "generations": g, "rows": len(rows)},
            ):
                params, opt_state, batch_stats, _lr_out, _wd_out, ys = run(
                    params, opt_state, batch_stats, base_keys, pbt_keys,
                    jnp.asarray(pbt_row_lr), jnp.asarray(pbt_row_wd),
                    data.x_train, data.y_train, data.x_val, data.y_val,
                    data.val_mask,
                    jnp.arange(gen0, gen0 + g), jnp.float32(obj_scale),
                )
                tls_all = np.asarray(ys[0])                   # (g, K, iv)
                ms_all = {k: np.asarray(v) for k, v in ys[1].items()}
                scores_all = np.asarray(ys[2], np.float32)    # (g, K)
                src_all = np.asarray(ys[3])
                newlr_all = np.asarray(ys[4], np.float32)
                newwd_all = np.asarray(ys[5], np.float32)
                expl_all = np.asarray(ys[6])
            if watchdog is not None:
                watchdog.untrack("dispatch")
            cold_dispatch = False
            from distributed_machine_learning_tpu.ckpt import get_metrics

            get_metrics().add("steps", g * iv)
            compile_delta = tracker.thread_seconds() - c0
            exec_s = max(time.time() - t0 - compile_delta, 0.0)
            _progress_note(
                f"dispatch synced: {exec_s:.1f}s execute + "
                f"{compile_delta:.1f}s compile"
            )
            if compile_delta > 0.05:
                compile_cost_s = compile_delta
            program.dispatch_obs.append({
                "chunk": g * iv, "rows": len(rows),
                "exec_s": exec_s, "compile_s": compile_delta,
            })
            del program.dispatch_obs[:-32]
            per_epoch_exec = exec_s / (g * iv)
            exec_ema = (
                per_epoch_exec if exec_ema is None
                else 0.5 * (exec_ema + per_epoch_exec)
            )
            exec_total_s += exec_s
            row_epochs += len(rows) * g * iv
            pbt_counters["host_dispatches"] += 1
            pbt_counters["generations"] += g

            t_end = time.time()
            total_e = g * iv
            emit_span = _obs.span("vec.emit")
            emit_cost = _new_emit_cost()
            for gi in range(g):
                gen = gen0 + gi
                for e_off in range(iv):
                    epoch = gen * iv + e_off
                    train_losses = tls_all[gi, :, e_off]
                    metrics_np = {k: v[gi, :, e_off]
                                  for k, v in ms_all.items()}
                    metrics_np = _inject_objective(
                        pbt, obj_scale, train_losses, metrics_np
                    )
                    step_count = (epoch + 1) * program.steps_per_epoch
                    shape_val = float(program.shape_schedule(
                        min(step_count, program.total_steps)
                    ))
                    now = (t0 + ((gi * iv + e_off) + 1)
                           * (t_end - t0) / total_e)
                    _emit_epoch_records(
                        batch, rows, active, lrs, epoch, step_count,
                        shape_val, now, train_losses, metrics_np,
                        pbt_notes, pbt, sched, searcher, store, metric,
                        mode, safe_cb, stop_rules, emit_cost,
                    )
                # Mirror this generation's in-device decisions into the
                # host bookkeeping; notes annotate the NEXT generation's
                # first record, exactly like the boundary path.
                pbt._generation_log.append({
                    "gen": gen,
                    "fire": bool(((gen + 1) * iv) < program.num_epochs),
                    "scores": scores_all[gi].copy(),
                    "row_lr": pbt_row_lr.copy(),
                    "row_wd": pbt_row_wd.copy(),
                    "valid": np.asarray([r >= 0 for r in rows]),
                    "src": src_all[gi].copy(),
                    "new_lr": newlr_all[gi].copy(),
                    "new_wd": newwd_all[gi].copy(),
                    "exploited": expl_all[gi].copy(),
                })
                pairs = _apply_reference_exploits(
                    batch, rows, lrs, wds, pbt, pbt_notes,
                    src_all[gi], newlr_all[gi], newwd_all[gi],
                    expl_all[gi], mut_keys,
                )
                pbt_counters["exploits"] += len(pairs)
                pbt_counters["explores"] += len(pairs) * len(mut_keys)
                if pairs:
                    log(
                        f"PBT epoch {(gen + 1) * iv - 1} (in-device): "
                        + ", ".join(f"{a}<-{b}" for a, b in pairs)
                    )
                pbt_row_lr = newlr_all[gi].copy()
                pbt_row_wd = newwd_all[gi].copy()
            _close_emit_span(emit_span, emit_cost)
            emit_span.end()
            safe_cb("on_heartbeat")
            epoch0 += g * iv
            if (
                ckpt_every
                and ckpt_path
                and epoch0 < program.num_epochs
                and (epoch0 // ckpt_every) > ((epoch0 - g * iv) // ckpt_every)
            ):
                save_population(epoch0)
            continue
        chunk = min(dispatch, epoch_budget - epoch0)
        _progress_note(
            f"dispatch epochs {epoch0}..{epoch0 + chunk} over "
            f"{len(rows)} rows (first dispatch of a shape traces+compiles)"
        )
        c0 = tracker.thread_seconds()
        t0 = time.time()
        if watchdog is not None:
            # One tracked entry per blocking dispatch: the monitor thread
            # flags it (stderr diagnostics + counter) if the device never
            # syncs within the deadline.  A chaos-injected hang exercises
            # exactly this path.
            watchdog.track(
                "dispatch",
                info={
                    "epoch0": epoch0, "epoch_end": epoch0 + chunk,
                    "rows": len(rows),
                },
                first_beat_grace_s=None if cold_dispatch else 0.0,
            )
        from distributed_machine_learning_tpu import chaos as _chaos

        _plan = _chaos.active_plan()
        if _plan is not None:
            _plan.maybe_hang_dispatch("vectorized", epoch0 + 1)
        with _obs.span(
            "vec.dispatch",
            {"epoch0": epoch0, "epochs": chunk, "rows": len(rows)},
        ):
            if chunk == 1:
                with _obs.span("vec.launch"):
                    epoch_keys = jax.vmap(
                        lambda key: jax.random.fold_in(key, epoch0)
                    )(base_keys)
                    params, opt_state, batch_stats, tl = program.train_epoch(
                        params, opt_state, batch_stats,
                        data.x_train, data.y_train,
                        epoch_keys,
                    )
                    metrics_k = program.eval_population(
                        params, batch_stats, data.x_val, data.y_val,
                        data.val_mask
                    )
                with _obs.span("vec.sync"):
                    tl_chunk = np.asarray(tl)[:, None]  # (K, 1)
                    metrics_chunk = {
                        key: np.asarray(v)[:, None]
                        for key, v in metrics_k.items()
                    }
            else:
                with _obs.span("vec.launch"):
                    params, opt_state, batch_stats, tls, ms = (
                        program.train_epochs(
                            params, opt_state, batch_stats, base_keys,
                            data.x_train, data.y_train,
                            data.x_val, data.y_val, data.val_mask,
                            jnp.arange(epoch0, epoch0 + chunk),
                        )
                    )
                with _obs.span("vec.sync"):
                    # vmap(scan) stacks as (K, E)
                    tl_chunk = np.asarray(tls)
                    metrics_chunk = {
                        key: np.asarray(v) for key, v in ms.items()
                    }
        # Materialize BEFORE reading the clocks: eval execution is part of
        # the per-epoch cost the compaction model weighs (np.asarray above
        # synced everything).
        if watchdog is not None:
            watchdog.untrack("dispatch")
        cold_dispatch = False
        # Dispatch boundary = `chunk` training epochs completed: the ckpt
        # overlap counters credit an async population save that was still
        # writing while these epochs ran on device.
        from distributed_machine_learning_tpu.ckpt import get_metrics

        get_metrics().add("steps", chunk)
        compile_delta = tracker.thread_seconds() - c0
        exec_s = max(time.time() - t0 - compile_delta, 0.0)
        _progress_note(
            f"dispatch synced: {exec_s:.1f}s execute + "
            f"{compile_delta:.1f}s compile"
        )
        if compile_delta > 0.05:
            compile_cost_s = compile_delta
        program.dispatch_obs.append({
            "chunk": chunk, "rows": len(rows),
            "exec_s": exec_s, "compile_s": compile_delta,
        })
        del program.dispatch_obs[:-32]  # bounded history
        per_epoch_exec = exec_s / chunk
        exec_ema = (
            per_epoch_exec if exec_ema is None
            else 0.5 * (exec_ema + per_epoch_exec)
        )
        exec_total_s += exec_s
        row_epochs += len(rows) * chunk
        if pbt is not None:
            pbt_counters["host_dispatches"] += 1

        t_end = time.time()
        with _obs.span("vec.emit") as emit_span:
            emit_cost = _new_emit_cost()
            for e_off in range(chunk):
                epoch = epoch0 + e_off
                train_losses = tl_chunk[:, e_off]
                metrics_np = {
                    key: v[:, e_off] for key, v in metrics_chunk.items()
                }
                metrics_np = _inject_objective(
                    pbt, obj_scale, train_losses, metrics_np
                )
                step_count = (epoch + 1) * program.steps_per_epoch
                # Trial-independent: evaluate once per epoch, not per trial.
                shape_val = float(program.shape_schedule(
                    min(step_count, program.total_steps)
                ))
                # Per-epoch completion time is interpolated across the
                # chunk so timestamp/time_total_s stay monotone and
                # ~epoch-granular (the device finished epoch e_off at
                # roughly this point).
                now = t0 + (e_off + 1) * (t_end - t0) / chunk
                _emit_epoch_records(
                    batch, rows, active, lrs, epoch, step_count, shape_val,
                    now, train_losses, metrics_np, pbt_notes, pbt, sched,
                    searcher, store, metric, mode, safe_cb, stop_rules,
                    emit_cost,
                )
            _close_emit_span(emit_span, emit_cost)
        epoch0 += chunk
        epoch = epoch0 - 1  # last completed epoch (PBT/compaction below)
        train_losses = tl_chunk[:, -1]
        metrics_np = {key: v[:, -1] for key, v in metrics_chunk.items()}
        # One heartbeat per dispatch: ProfilerCallback bounds its trace
        # window on this hook (callbacks.py), same as tune.run's event loop.
        safe_cb("on_heartbeat")

        # ---- vectorized PBT (boundary mode): exploit = one gather ----------
        # A chunk may cross interval boundaries; fire when it did (at worst
        # the perturbation lands chunk-1 epochs late — document, don't drop).
        # Compiled mode never reaches here mid-sweep: its generation scan
        # fires every interval in-program, and the only per-epoch chunks it
        # dispatches are trailing leftovers past the final generation.
        if (
            pbt is not None
            and not pbt_compiled
            and (epoch0 // pbt.interval) > ((epoch0 - chunk) // pbt.interval)
            and epoch0 < program.num_epochs
        ):
            if pbt.metric in metrics_np:
                scores = metrics_np[pbt.metric]
            elif pbt.metric == "train_loss":
                scores = train_losses
            else:
                raise ValueError(
                    f"PBT metric {pbt.metric!r} is not produced by this "
                    f"trainable (have: train_loss, "
                    f"{', '.join(sorted(metrics_np))})"
                )
            pbt_counters["generations"] += 1
        if (
            pbt is not None
            and not pbt_compiled
            and pbt_spec is not None
            and (epoch0 // pbt.interval) > ((epoch0 - chunk) // pbt.interval)
            and epoch0 < program.num_epochs
        ):
            # Deterministic reference step — the exact host-side twin of
            # the compiled generation step (shared draw bits, shared f32
            # arithmetic), so pbt_mode="boundary" reproduces the compiled
            # path's decisions bit for bit.  PB2 and non-continuous specs
            # take the legacy branch below instead.
            from distributed_machine_learning_tpu.tune.schedulers.pbt import (
                generation_draw_count,
                generation_draws,
                reference_generation_step,
            )

            gen = (epoch0 - 1) // pbt.interval
            valid = np.asarray([r >= 0 and active[r] for r in rows])
            draws = generation_draws(
                pbt.seed, len(rows), gen, generation_draw_count(pbt_spec)
            )
            scores_f = (np.asarray(scores, np.float32)
                        * np.float32(obj_scale))
            src, new_lr, new_wd, exploited = reference_generation_step(
                pbt_spec, scores_f, pbt_row_lr, pbt_row_wd, valid, draws,
                True,
            )
            pbt._generation_log.append({
                "gen": gen, "fire": True,
                "scores": scores_f.copy(),
                "row_lr": pbt_row_lr.copy(),
                "row_wd": pbt_row_wd.copy(),
                "valid": valid,
                "src": src.copy(), "new_lr": new_lr.copy(),
                "new_wd": new_wd.copy(), "exploited": exploited.copy(),
            })
            pairs = _apply_reference_exploits(
                batch, rows, lrs, wds, pbt, pbt_notes,
                src, new_lr, new_wd, exploited, mut_keys,
            )
            pbt_counters["exploits"] += len(pairs)
            pbt_counters["explores"] += len(pairs) * len(mut_keys)
            if pairs:
                sel = jnp.asarray(src)
                # Exploit: bottom rows adopt donor rows' weights AND
                # optimizer state in one device-side gather; explore lands
                # in the injected optimizer hyperparams.
                params, opt_state, batch_stats = jax.tree.map(
                    lambda a: a[sel], (params, opt_state, batch_stats)
                )
                opt_state = _set_hyperparams(
                    opt_state, jnp.asarray(new_lr), jnp.asarray(new_wd)
                )
                if pop_sharding is not None:
                    params, opt_state, batch_stats = jax.device_put(
                        (params, opt_state, batch_stats), pop_sharding
                    )
                log(
                    f"PBT epoch {epoch}: "
                    + ", ".join(f"{a}<-{b}" for a, b in pairs)
                )
            pbt_row_lr = new_lr.copy()
            pbt_row_wd = new_wd.copy()
        elif (
            pbt is not None
            and not pbt_compiled
            and (epoch0 // pbt.interval) > ((epoch0 - chunk) // pbt.interval)
            and epoch0 < program.num_epochs
        ):
            sign = 1.0 if pbt.mode == "min" else -1.0

            def rank_key(value: float) -> float:
                # Non-finite rows must never donate (a NaN donor would
                # corrupt healthy trials wholesale) and should be first in
                # line for rescue — rank them strictly worst.
                v = sign * value
                return v if np.isfinite(v) else np.inf

            # active[r]: a stopper (stop=) can now terminate rows under
            # PBT — a TERMINATED row must neither donate (its metrics
            # stopped being meaningful) nor be "rescued" (mutating a
            # completed trial's config after on_trial_complete consumed it).
            live = sorted(
                (rank_key(float(scores[i])), i, r)
                for i, r in enumerate(rows)
                if r >= 0 and active[r]
            )
            if len(live) >= 4 and np.isfinite(live[0][0]):
                q = max(1, int(len(live) * pbt.quantile))
                # Donors must be finite (fewer than q finite rows -> smaller
                # donor pool, never an inf-ranked one).
                top = [t for t in live[:q] if np.isfinite(t[0])]
                bottom = live[-q:]
                src = np.arange(len(rows))
                exploited = []
                for _, i, r in bottom:
                    rng = rng_from(
                        "vpbt", pbt.seed, batch[r].trial_id, epoch + 1
                    )
                    _, di, dr = top[int(rng.integers(len(top)))]
                    src[i] = di
                    donor, lagger = batch[dr], batch[r]
                    # Explore: mutate the donor's hyperparams; the laggard
                    # keeps its own identity/seed (its PRNG row stays put).
                    new_cfg = pbt._mutate(dict(donor.config), rng)
                    new_cfg["seed"] = lagger.config.get("seed", 0)
                    lagger.config = new_cfg
                    # The laggard's weights are about to be replaced by the
                    # donor's: a score delta across that boundary would
                    # credit the new config with the donor's head start.
                    pbt.reset_improvement_chain(lagger.trial_id)
                    lrs[r] = float(new_cfg["learning_rate"])
                    wds[r] = float(new_cfg.get("weight_decay", 0.0))
                    pbt_notes[r] = donor.trial_id
                    exploited.append((lagger.trial_id, donor.trial_id))
                    pbt._num_perturbations += 1
                pbt_counters["exploits"] += len(exploited)
                pbt_counters["explores"] += (
                    len(exploited) * len(pbt.mutations)
                )
                if exploited:
                    sel = jnp.asarray(src)
                    # Exploit: bottom rows adopt donor rows' weights AND
                    # optimizer state in one device-side gather.
                    params, opt_state, batch_stats = jax.tree.map(
                        lambda a: a[sel], (params, opt_state, batch_stats)
                    )
                    # Explore lands in the optimizer state: per-row lr/wd
                    # live in the injected hyperparams arrays.
                    opt_state = _set_hyperparams(
                        opt_state,
                        jnp.asarray([lrs[r] if r >= 0 else float(lrs[0])
                                     for r in rows], jnp.float32),
                        jnp.asarray([wds[r] if r >= 0 else float(wds[0])
                                     for r in rows], jnp.float32),
                    )
                    if pop_sharding is not None:
                        params, opt_state, batch_stats = jax.device_put(
                            (params, opt_state, batch_stats), pop_sharding
                        )
                    log(
                        f"PBT epoch {epoch}: "
                        + ", ".join(f"{a}<-{b}" for a, b in exploited)
                    )

        if not any(active[r] for r in rows if r >= 0):
            log(f"population fully early-stopped at epoch {epoch}")
            break

        # Compaction: once survivors fit in half the rows, slice them out and
        # continue as a smaller vmapped program (halving boundaries bound the
        # number of distinct compiled population sizes to log2(K)).  A new
        # size means an XLA recompile, so "auto" only compacts when the
        # measured epoch savings outweigh the measured compile cost.
        pos = [i for i, r in enumerate(rows) if r >= 0 and active[r]]
        remaining = epoch_budget - epoch - 1
        target = len(rows) // 2
        if size_multiple > 1:
            target = (target // size_multiple) * size_multiple
        if compaction != "never" and remaining > 0 and 0 < len(pos) <= target:
            if compaction == "always":
                worth_it = True
            else:
                saved_s = remaining * (exec_ema or 0.0) * 0.5
                # Price the recompile pessimistically: the HALVED size may
                # never have been compiled anywhere, so use the worst single
                # backend compile this process has paid (not just the last
                # delta, which is ~0 after a persistent-cache hit).
                cost_s = max(
                    compile_cost_s or 0.0, tracker.max_backend_compile_s()
                )
                worth_it = saved_s > cost_s
            if worth_it:
                # Compact to EXACTLY half (padding with already-stopped rows
                # if survivors undershoot): sizes walk the fixed ladder
                # K, K/2, K/4, ..., so every sweep with the same K reuses the
                # same compiled programs — across chunks AND across runs via
                # the persistent cache.
                pad = [i for i in range(len(rows)) if i not in set(pos)]
                keep = sorted(pos + pad[: target - len(pos)])
                with _obs.span("vec.compact", {
                    "rows_before": len(rows), "rows_after": len(keep),
                }):
                    sel = jnp.asarray(keep)
                    params, opt_state, batch_stats = jax.tree.map(
                        lambda a: a[sel], (params, opt_state, batch_stats)
                    )
                    base_keys = base_keys[sel]
                    if pop_sharding is not None:
                        params, opt_state, batch_stats, base_keys = (
                            jax.device_put(
                                (params, opt_state, batch_stats, base_keys),
                                pop_sharding,
                            )
                        )
                rows = [rows[i] for i in keep]
                cold_dispatch = True  # halved size = fresh compile next
                log(
                    f"compacted population -> {len(rows)} rows "
                    f"({len(pos)} live) at epoch {epoch}"
                )

        # Population checkpoint (preemption tolerance): save AFTER PBT and
        # compaction so the state on disk matches the row mapping.
        if (
            ckpt_every
            and ckpt_path
            and epoch0 < program.num_epochs
            and (epoch0 // ckpt_every) > ((epoch0 - chunk) // ckpt_every)
        ):
            save_population(epoch0)

    # ---- quality_after_quant: post-quantization final scoring --------------
    # The PBT generations ranked on pure quality (the scalarization factor
    # is a frozen constant — bit-parity contract); what the SWEEP selects
    # on is measured here instead: every surviving row is int8
    # fake-quantized host-side (per-row, per-channel scales — exactly what
    # its own export would write) and re-scored on the validation split
    # through the already-compiled population eval (same shapes/dtypes, so
    # zero new programs).  One final record per live trial carries the
    # int8 validation MAPE as ``pbt_objective`` + ``quant_mape`` —
    # ``ExperimentAnalysis(metric="pbt_objective")`` then picks the winner
    # that survives quantization.
    if pbt is not None and getattr(pbt, "quant_aware", False):
        from distributed_machine_learning_tpu.quant import (
            fake_quant_population,
        )

        q_metrics = {
            k: np.asarray(v)
            for k, v in program.eval_population(
                jax.tree.map(
                    jnp.asarray,
                    fake_quant_population(jax.tree.map(np.asarray, params)),
                ),
                batch_stats, data.x_val, data.y_val, data.val_mask,
            ).items()
        }
        pbt_counters["quant_evals"] = pbt_counters.get("quant_evals", 0) + 1
        q_now = time.time()
        for i, r in enumerate(rows):
            if r < 0 or not active[r]:
                continue
            trial = batch[r]
            q_mape = float(q_metrics["validation_mape"][i])
            record = {
                "epoch": epoch0 - 1,
                "training_iteration": trial.reports_since_restart,
                "trial_id": trial.trial_id,
                "timestamp": q_now,
                "time_total_s": q_now - trial.started_at,
                "quant_precision": "int8",
                "quant_mape": q_mape,
                "pbt_objective": q_mape,
            }
            trial.results.append(record)
            store.append_result(trial, record)
            safe_cb("on_trial_result", trial, record)

    now = time.time()
    for i, trial in enumerate(batch):
        if active[i]:
            trial.status = TrialStatus.TERMINATED
            trial.finished_at = now
            sched.on_trial_complete(trial)
            searcher.on_trial_complete(
                trial.trial_id, trial.config, trial.last_result, metric, mode
            )
            safe_cb("on_trial_complete", trial)
    return row_epochs, exec_total_s

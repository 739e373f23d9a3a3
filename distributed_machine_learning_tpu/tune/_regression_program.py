"""Shared pieces of the built-in regression workload.

Single source of truth for the forward-call convention, the jittable
epoch/eval program bodies, and validation padding — used by both the
per-trial trainable (``tune/trainable.py``) and the vmapped population
runner (``tune/vectorized.py``), so a numerics change lands in both paths.

Capability lineage: this is the reference's L2 training loop
(`/root/reference/ray-tune-hpo-regression.py:260-373`) re-shaped for XLA —
an epoch is one ``lax.scan`` program, eval is a padded masked scan with
static shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax


def make_forward(model, flag_name: str, has_bn: bool) -> Callable:
    """Unified apply() over the zoo's two call conventions.

    Returns ``forward(params, batch_stats, x, dropout_key, train) ->
    (preds, new_batch_stats, aux_loss)``.  ``aux_loss`` collects everything
    the model sowed into the ``"moe"`` collection (the MoE load-balance
    terms, already scaled by their coefficient — models/moe.py); it is 0.0
    for dense models and is added to the training objective only.
    """

    from distributed_machine_learning_tpu.models.moe import collect_aux

    def forward(params, batch_stats, x, dropout_key, train: bool):
        vs = {"params": params}
        if has_bn:
            vs["batch_stats"] = batch_stats
        kwargs = {flag_name: (not train) if flag_name == "deterministic" else train}
        rngs = {"dropout": dropout_key} if train else None
        mutable = ["moe"] + (["batch_stats"] if has_bn and train else [])
        out, mut = model.apply(vs, x, rngs=rngs, mutable=mutable, **kwargs)
        new_bs = mut["batch_stats"] if (has_bn and train) else batch_stats
        return out, new_bs, collect_aux(mut)

    return forward


def _widened(loss_fn: Callable, preds: jnp.ndarray) -> jnp.ndarray:
    """Predictions as the loss takes them: float32, but as they are for a
    loss that widens them itself (``cross_entropy`` does, a chunk of the
    sequence at a time: ops/losses.py)."""
    if getattr(loss_fn, "widens_itself", False):
        return preds
    return preds.astype(jnp.float32)


def per_example_losses(preds: jnp.ndarray, targets: jnp.ndarray):
    """Per-example squared error, absolute error, and APE (for masked eval)."""
    se = jnp.mean((preds - targets) ** 2, axis=-1)
    ae = jnp.mean(jnp.abs(preds - targets), axis=-1)
    ape = jnp.mean(jnp.abs(targets - preds) / (jnp.abs(targets) + 1e-8), axis=-1)
    return se, ae, ape


def make_epoch_fn(
    forward: Callable,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
    n_train: int,
    num_batches: int,
    batch_size: int,
) -> Callable:
    """One training epoch as a pure function: shuffle + scan over batches.

    ``epoch(params, opt_state, batch_stats, x_all, y_all, epoch_key) ->
    (params, opt_state, batch_stats, mean_loss)``.  Jit/vmap at the call
    site.
    """

    def epoch(params, opt_state, batch_stats, x_all, y_all, epoch_key):
        perm_key, drop0 = jax.random.split(epoch_key)
        perm = jax.random.permutation(perm_key, n_train)
        perm = perm[: num_batches * batch_size].reshape(num_batches, batch_size)

        def step(carry, idx):
            params, opt_state, batch_stats, key = carry
            key, dkey = jax.random.split(key)
            xb, yb = x_all[idx], y_all[idx]

            def loss_of(p):
                preds, new_bs, aux = forward(p, batch_stats, xb, dkey, train=True)
                return loss_fn(_widened(loss_fn, preds), yb) + aux, new_bs

            (loss, new_bs), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params
            )
            updates, new_opt = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, new_opt, new_bs, key), loss

        (params, opt_state, batch_stats, _), losses = jax.lax.scan(
            step, (params, opt_state, batch_stats, drop0), perm
        )
        return params, opt_state, batch_stats, losses.mean()

    return epoch


def make_chunk_epoch_fn(
    forward: Callable,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
) -> Callable:
    """One streaming CHUNK of an epoch as a pure function (out-of-core
    path, ``data/pipeline.py``): a scan over a staged slab of pre-gathered
    batches.

    ``chunk(params, opt_state, batch_stats, key, xb, yb) -> (params,
    opt_state, batch_stats, key, losses)`` where ``xb``/``yb`` are
    ``[rows, batch_size, ...]`` slabs.  The step body is kept IDENTICAL to
    :func:`make_epoch_fn`'s (same split order, same loss closure, same
    update sequence) and the PRNG key rides the carry ACROSS chunk calls,
    so a streaming epoch executes bit-for-bit the computation the resident
    epoch program executes — the host gathers the batches the resident
    program's in-program gather would have produced (same permutation:
    threefry draws are identical eager vs jit), and the chunk boundary is
    invisible to the numerics.  Jit at the call site with
    ``donate_argnums`` covering state AND the slab (the consumed chunk's
    buffers free at the boundary — the ring's memory bound depends on it).
    """

    def chunk(params, opt_state, batch_stats, key, xb, yb):
        def step(carry, batch):
            params, opt_state, batch_stats, key = carry
            key, dkey = jax.random.split(key)
            xb_, yb_ = batch

            def loss_of(p):
                preds, new_bs, aux = forward(p, batch_stats, xb_, dkey,
                                             train=True)
                return loss_fn(_widened(loss_fn, preds), yb_) + aux, new_bs

            (loss, new_bs), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params
            )
            updates, new_opt = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, new_opt, new_bs, key), loss

        (params, opt_state, batch_stats, key), losses = jax.lax.scan(
            step, (params, opt_state, batch_stats, key), (xb, yb)
        )
        return params, opt_state, batch_stats, key, losses

    return chunk


def make_indexed_epoch_fn(
    forward: Callable,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
) -> Callable:
    """The SHARDED trainable's fused epoch body (tune/trainable_sharded.py):
    a scan over pre-gathered ``[num_batches, global_batch, ...]`` slabs
    whose per-step dropout key is ``fold_in(epoch_key, i)`` on an integer
    step counter riding the carry — the indexed twin of
    :func:`make_epoch_fn` (which draws keys by splitting along the carry).

    ``epoch(params, opt_state, batch_stats, xb, yb, epoch_key) ->
    (params, opt_state, batch_stats, mean_loss)``.  Jit at the call site
    with donation + in/out shardings; extracted here so the jaxlint
    donation/hygiene audits (analysis/jaxlint/) lower the EXACT program
    the trainable runs, not a reimplementation that could drift.
    """

    def epoch(params, opt_state, batch_stats, xb, yb, epoch_key):
        def step(carry, batch):
            params, opt_state, batch_stats, i = carry
            x, y = batch
            key = jax.random.fold_in(epoch_key, i)

            def loss_of(p):
                preds, new_bs, aux = forward(p, batch_stats, x, key, True)
                return loss_fn(_widened(loss_fn, preds), y) + aux, new_bs

            (loss, new_bs), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, new_bs, i + 1), loss

        (params, opt_state, batch_stats, _), losses = jax.lax.scan(
            step, (params, opt_state, batch_stats, jnp.int32(0)), (xb, yb)
        )
        return params, opt_state, batch_stats, losses.mean()

    return epoch


def make_indexed_chunk_fn(
    forward: Callable,
    tx: optax.GradientTransformation,
    loss_fn: Callable,
) -> Callable:
    """The sharded trainable's streaming CHUNK body: the same step body as
    :func:`make_indexed_epoch_fn` scanned over a staged slab, with the
    global batch counter entering as ``i0`` so ``fold_in(epoch_key, i)``
    matches the resident program bit for bit across chunk boundaries.

    ``chunk(params, opt_state, batch_stats, i0, xb, yb, epoch_key) ->
    (params, opt_state, batch_stats, losses)``.  Jit at the call site.
    """

    def chunk(params, opt_state, batch_stats, i0, xb, yb, epoch_key):
        def step(carry, batch):
            params, opt_state, batch_stats, i = carry
            x, y = batch
            key = jax.random.fold_in(epoch_key, i)

            def loss_of(p):
                preds, new_bs, aux = forward(p, batch_stats, x, key, True)
                return loss_fn(_widened(loss_fn, preds), y) + aux, new_bs

            (loss, new_bs), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, new_bs, i + 1), loss

        (params, opt_state, batch_stats, _), losses = jax.lax.scan(
            step, (params, opt_state, batch_stats, i0), (xb, yb)
        )
        return params, opt_state, batch_stats, losses

    return chunk


def make_chunk_eval_fn(forward: Callable) -> Callable:
    """Masked eval over ONE streamed chunk of validation blocks: ``(params,
    batch_stats, xb, yb, mb) -> (se_sum, ae_sum, ape_sum, hub_sum, count)``
    partial sums the host accumulates across chunks before forming the
    :func:`make_eval_fn` metric set (same per-example terms; only the
    cross-block summation moves to the host)."""

    def evaluate_chunk(params, batch_stats, xb, yb, mb):
        def step(_, batch):
            x, y, m = batch
            preds, _, _ = forward(
                params, batch_stats, x, jax.random.key(0), train=False
            )
            preds = preds.astype(jnp.float32)
            se, ae, ape = per_example_losses(preds, y)
            hub = jnp.mean(optax.huber_loss(preds, y, delta=1.0), axis=-1)
            return None, (
                (se * m).sum(), (ae * m).sum(), (ape * m).sum(),
                (hub * m).sum(),
            )

        _, (se, ae, ape, hub) = jax.lax.scan(step, None, (xb, yb, mb))
        return se.sum(), ae.sum(), ape.sum(), hub.sum(), mb.sum()

    return evaluate_chunk


def eval_metrics_from_sums(
    loss_name: str, se: float, ae: float, ape: float, hub: float, count: float
) -> Dict[str, float]:
    """:func:`make_eval_fn`'s metric dict from host-accumulated partial
    sums (the streamed-validation path)."""
    count = max(float(count), 1e-9)
    mse = se / count
    mae = ae / count
    mape = 100.0 * ape / count
    huber = hub / count
    rmse = float(np.sqrt(mse))
    by_name = {
        "mse": mse, "mae": mae, "mape": mape, "huber": huber, "rmse": rmse,
    }
    return {
        "validation_loss": float(by_name.get(loss_name, mse)),
        "validation_mse": float(mse),
        "validation_rmse": float(rmse),
        "validation_mae": float(mae),
        "validation_mape": float(mape),
    }


# Metric names make_eval_fn produces (plus "train_loss" from the epoch fn):
# the keys a compiled PBT generation scan can rank on.  Kept next to the
# eval body so a metric rename cannot silently desynchronize the validator.
EVAL_METRIC_KEYS = (
    "validation_loss", "validation_mse", "validation_rmse",
    "validation_mae", "validation_mape",
)


def make_eval_fn(
    forward: Callable, loss_name: str, n_blocks: int, eval_bs: int
) -> Callable:
    """Masked blockwise eval: ``(params, batch_stats, x, y, mask) ->
    {validation_loss, _mse, _rmse, _mae, _mape}``.  Jit/vmap at the call
    site."""

    def evaluate(params, batch_stats, x_all, y_all, mask):
        xb = x_all.reshape(n_blocks, eval_bs, *x_all.shape[1:])
        yb = y_all.reshape(n_blocks, eval_bs, *y_all.shape[1:])
        mb = mask.reshape(n_blocks, eval_bs)

        def step(_, batch):
            x, y, m = batch
            preds, _, _ = forward(
                params, batch_stats, x, jax.random.key(0), train=False
            )
            preds = preds.astype(jnp.float32)
            se, ae, ape = per_example_losses(preds, y)
            hub = jnp.mean(optax.huber_loss(preds, y, delta=1.0), axis=-1)
            return None, (
                (se * m).sum(), (ae * m).sum(), (ape * m).sum(), (hub * m).sum()
            )

        _, (se, ae, ape, hub) = jax.lax.scan(step, None, (xb, yb, mb))
        count = mask.sum()
        mse = se.sum() / count
        mae = ae.sum() / count
        mape = 100.0 * ape.sum() / count
        huber = hub.sum() / count
        rmse = jnp.sqrt(mse)
        by_name = {
            "mse": mse, "mae": mae, "mape": mape, "huber": huber, "rmse": rmse,
        }
        return {
            "validation_loss": by_name.get(loss_name, mse),
            "validation_mse": mse,
            "validation_rmse": rmse,
            "validation_mae": mae,
            "validation_mape": mape,
        }

    return evaluate


def make_token_eval_fn(
    model, flag_name: str, n_blocks: int, eval_bs: int
) -> Callable:
    """Masked blockwise eval of a language model (a loss of
    ``ops.losses.TOKEN_LOSSES``): ``(params, batch_stats, x, y, mask) ->
    {validation_loss, validation_perplexity}``, the mean cross-entropy
    over the positions of the unmasked rows.  Where the model's expert
    layers sow their routing counts (``models/hybrid_lm.py``), the
    evaluation batches' counts ride along: ``moe_local_pairs`` (token-expert
    pairs computed here a batch, summed over the layers) and
    ``moe_load_max_over_mean`` (the fullest held expert over the mean one,
    the worst layer's)."""
    from distributed_machine_learning_tpu.models.moe import STATS_COLLECTION
    from distributed_machine_learning_tpu.ops.losses import token_cross_entropy

    kwargs = {flag_name: flag_name == "deterministic"}

    def evaluate(params, batch_stats, x_all, y_all, mask):
        del batch_stats  # no family with batch statistics predicts tokens
        xb = x_all.reshape(n_blocks, eval_bs, *x_all.shape[1:])
        yb = y_all.reshape(n_blocks, eval_bs, *y_all.shape[1:])
        mb = mask.reshape(n_blocks, eval_bs)

        def step(_, batch):
            x, y, m = batch
            logits, mut = model.apply(
                {"params": params}, x, mutable=[STATS_COLLECTION], **kwargs
            )
            nll = jnp.mean(token_cross_entropy(logits, y), axis=-1)
            sown = jax.tree_util.tree_leaves_with_path(
                mut.get(STATS_COLLECTION, {})
            )

            def layers(name):  # what every expert layer sowed under ``name``
                return [v for path, v in sown
                        if name in jax.tree_util.keystr(path)]

            pairs = [jnp.sum(v) for v in layers("local_pairs")]
            loads = [jnp.max(v) for v in layers("load_max_over_mean")]
            routing = (sum(pairs), jnp.max(jnp.stack(loads))) if pairs else ()
            return None, ((nll * m).sum(), routing)

        _, (nll, routing) = jax.lax.scan(step, None, (xb, yb, mb))
        loss = nll.sum() / mask.sum()
        out = {"validation_loss": loss, "validation_perplexity": jnp.exp(loss)}
        if routing:
            out["moe_local_pairs"] = routing[0].mean()
            out["moe_load_max_over_mean"] = routing[1].max()
        return out

    return evaluate


@dataclass
class StagedData:
    """Device-resident dataset + padded validation block layout."""

    x_train: jnp.ndarray
    y_train: jnp.ndarray
    x_val: jnp.ndarray
    y_val: jnp.ndarray
    val_mask: jnp.ndarray
    n_train: int
    num_batches: int
    batch_size: int
    n_val_blocks: int
    eval_bs: int


def stage_data(
    train_data, val_data, batch_size: int, compute_dtype
) -> StagedData:
    """Stage both splits to device once; pad validation to whole blocks."""
    n_train = len(train_data)
    batch_size = int(min(batch_size, n_train))
    num_batches = max(n_train // batch_size, 1)

    n_val = len(val_data)
    eval_bs = int(min(max(batch_size, 1), n_val))
    n_val_pad = -(-n_val // eval_bs) * eval_bs
    pad = n_val_pad - n_val

    x_val = (
        np.concatenate(
            [val_data.x, np.zeros((pad, *val_data.x.shape[1:]), val_data.x.dtype)]
        )
        if pad
        else val_data.x
    )
    y_val = (
        np.concatenate(
            [val_data.y, np.zeros((pad, *val_data.y.shape[1:]), val_data.y.dtype)]
        )
        if pad
        else val_data.y
    )
    def staged(a, float_dtype):
        # Token ids stay integers: no float of the compute dtype holds them.
        integer = np.issubdtype(np.asarray(a).dtype, np.integer)
        return jnp.asarray(a, dtype=jnp.int32 if integer else float_dtype)

    return StagedData(
        x_train=staged(train_data.x, compute_dtype),
        y_train=staged(train_data.y, jnp.float32),
        x_val=staged(x_val, compute_dtype),
        y_val=staged(y_val, jnp.float32),
        val_mask=jnp.asarray(
            np.concatenate([np.ones(n_val, np.float32), np.zeros(pad, np.float32)])
        ),
        n_train=n_train,
        num_batches=num_batches,
        batch_size=batch_size,
        n_val_blocks=n_val_pad // eval_bs,
        eval_bs=eval_bs,
    )


def make_pbt_generation_fn(
    epoch_fn: Callable,
    eval_fn: Callable,
    spec: Dict[str, Any],
    *,
    interval: int,
    num_epochs_total: int,
    metric: str,
    n_rows: int,
    n_valid: int,
):
    """The whole-PBT-sweep program body: a ``lax.scan`` over generations.

    Each generation = ``interval`` epochs of the fused per-row epoch scan
    (vmapped over the population) -> in-program quantile ranking over the
    per-row metric -> exploit as gather (bottom-quantile rows adopt
    top-quantile rows' params AND optimizer state) -> explore as
    PRNG-driven per-row perturbation of the injected lr/wd (per-row keys
    travel with their rows; a lagger keeps its own identity/seed).  This
    is the Podracer "Anakin" shape applied to HPO: the host dispatches
    once per generation CHUNK, not once per perturbation.

    Every decision op is chosen for bit-parity with
    ``schedulers.pbt.reference_generation_step``: threefry draws (jit ==
    eager), stable lexsort ranking, IEEE f32 multiply/clip, and grid-gather
    resampling (no transcendentals — XLA's fused exp is not bit-stable vs
    eager).  Per-generation decisions come back as stacked scan outputs
    (scores, src, new lr/wd, exploited) so the driver reconstructs trial
    records, ``pbt_exploited_from`` notes, and TB streams exactly as rich
    as the host-boundary path.

    Returns ``run(params, opt_state, batch_stats, base_keys, pbt_keys,
    lr, wd, x, y, xv, yv, mask, gen_ids, obj_scale)`` for the caller to
    jit with ``donate_argnums=(0, 1, 2)``.  ``obj_scale`` is the host-
    measured objective scalarization factor (latency/param terms — a
    constant row multiplier, so in-population ranking is unchanged but
    emitted scores are the deployability-scalarized objective).
    """
    if metric != "train_loss" and metric not in EVAL_METRIC_KEYS:
        raise ValueError(
            f"PBT metric {metric!r} is not produced by this trainable "
            f"(have: train_loss, {', '.join(EVAL_METRIC_KEYS)})"
        )
    from distributed_machine_learning_tpu.ops.optimizers import (
        set_injected_hyperparams,
    )
    from distributed_machine_learning_tpu.tune.schedulers.pbt import (
        generation_draw_count,
        resample_grid,
    )

    sign = np.float32(spec["sign"])
    q = max(1, int(n_valid * spec["quantile"]))
    lag_start = max(q, n_valid - q)
    exploit_possible = n_valid >= 4 and lag_start < n_valid
    n_draws = generation_draw_count(spec)
    n_factors = len(spec["factors"])
    factors_c = np.asarray(spec["factors"], np.float32)
    grids = {e["key"]: resample_grid(e, spec["grid_points"])
             for e in spec["specs"]}
    invalid_c = (np.arange(n_rows) >= n_valid).astype(np.int8)
    resample_p = np.float32(spec["resample_p"])

    def exploit_explore(scores, lr, wd, draws, fire):
        if not exploit_possible:
            return (
                jnp.arange(n_rows),
                lr, wd,
                jnp.zeros((n_rows,), bool),
            )
        rank = jnp.where(
            jnp.isfinite(scores * sign), scores * sign, jnp.inf
        ).astype(jnp.float32)
        # Stable three-key sort: valid rows first, best score first, ties
        # by row index — identical to the reference's sorted() tuple key.
        order = jnp.lexsort((jnp.arange(n_rows), rank, invalid_c))
        donors = order[:q]
        donor_ok = jnp.isfinite(rank[donors])
        n_ok = donor_ok.sum()
        enabled = fire & jnp.isfinite(rank[order[0]]) & (n_ok > 0)
        # Finite donors, original donor order first (stable partition).
        fd = donors[jnp.lexsort((jnp.arange(q),
                                 (~donor_ok).astype(jnp.int8)))]
        laggers = order[lag_start:n_valid]
        u0 = draws[laggers, 0]
        d_idx = jnp.clip(
            (u0 * n_ok.astype(jnp.float32)).astype(jnp.int32),
            0, jnp.maximum(n_ok - 1, 0),
        )
        donor_rows = fd[d_idx]
        src = jnp.arange(n_rows).at[laggers].set(
            jnp.where(enabled, donor_rows, laggers)
        )
        exploited = jnp.zeros((n_rows,), bool).at[laggers].set(enabled)
        vals = {"learning_rate": lr, "weight_decay": wd}
        out = {}
        for m, e in enumerate(spec["specs"]):
            base = vals[e["key"]]
            donor_v = base[src]
            u_res = draws[:, 1 + 2 * m]
            u_val = draws[:, 2 + 2 * m]
            grid = jnp.asarray(grids[e["key"]])
            gi = jnp.clip(
                (u_val * np.float32(len(grids[e["key"]]))).astype(jnp.int32),
                0, len(grids[e["key"]]) - 1,
            )
            resampled = grid[gi]
            fi = jnp.clip(
                (u_val * np.float32(n_factors)).astype(jnp.int32),
                0, n_factors - 1,
            )
            stepped = jnp.clip(
                donor_v * jnp.asarray(factors_c)[fi],
                np.float32(e["lo"]), np.float32(e["hi"]),
            )
            cand = jnp.where(u_res < resample_p, resampled, stepped)
            out[e["key"]] = jnp.where(exploited, cand, base)
        for key in ("learning_rate", "weight_decay"):
            if key not in spec["keys"]:
                # Exploit copies the donor's whole config: an unmutated
                # hyperparam still adopts the donor's value.
                out[key] = jnp.where(exploited, vals[key][src], vals[key])
        return src, out["learning_rate"], out["weight_decay"], exploited

    def run(params, opt_state, batch_stats, base_keys, pbt_keys, lr, wd,
            x, y, xv, yv, mask, gen_ids, obj_scale):
        def one_row(p, o, b, key, epoch_ids):
            def ebody(carry, e):
                p, o, b = carry
                k = jax.random.fold_in(key, e)
                p, o, b, tl = epoch_fn(p, o, b, x, y, k)
                m = eval_fn(p, b, xv, yv, mask)
                return (p, o, b), (tl, m)

            (p, o, b), (tls, ms) = jax.lax.scan(ebody, (p, o, b), epoch_ids)
            return p, o, b, tls, ms

        v_epochs = jax.vmap(one_row, in_axes=(0, 0, 0, 0, None))

        def gen_body(carry, gen):
            p, o, b, lr, wd = carry
            epoch_ids = gen * interval + jnp.arange(interval)
            p, o, b, tls, ms = v_epochs(p, o, b, base_keys, epoch_ids)
            sel = tls if metric == "train_loss" else ms[metric]
            scores = sel[:, -1] * obj_scale
            draws = jax.vmap(
                lambda k2: jax.random.uniform(
                    jax.random.fold_in(k2, gen), (n_draws,)
                )
            )(pbt_keys)
            # No perturbation after the sweep's final epoch (matching the
            # boundary path's `epoch0 < num_epochs` guard).
            fire = ((gen + 1) * interval) < num_epochs_total
            src, new_lr, new_wd, exploited = exploit_explore(
                scores, lr, wd, draws, fire
            )
            p, o, b = jax.tree.map(lambda a: a[src], (p, o, b))
            o = set_injected_hyperparams(o, new_lr, new_wd)
            return (p, o, b, new_lr, new_wd), (
                tls, ms, scores, src, new_lr, new_wd, exploited
            )

        (p, o, b, lr, wd), ys = jax.lax.scan(
            gen_body, (params, opt_state, batch_stats, lr, wd), gen_ids
        )
        return p, o, b, lr, wd, ys

    return run


def _call_lacks_deterministic(model) -> bool:
    """Whether ``model.__call__`` provably has no ``deterministic``
    parameter (explicit signature, no ``**kwargs``).  Inconclusive
    signatures return False — the caller then re-raises rather than
    guessing."""
    import inspect

    try:
        params = inspect.signature(type(model).__call__).parameters
    except (TypeError, ValueError):
        return False
    if any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    ):
        return False
    return "deterministic" not in params


def detect_call_convention(model, sample_x, init_rngs=None,
                           abstract=False):
    """Init the model and learn (variables, train-flag kwarg name).

    The init is jitted: eager ``model.init`` dispatches hundreds of tiny ops
    one by one, each paying a host->device dispatch; one
    compiled executable makes trial startup near-constant.  The rng dict is
    a traced ARGUMENT, so trials with different ``init_rngs`` (per-trial
    init diversity — the reference's torch trials each start from their own
    random init) share one compiled init program.

    ``abstract=True`` runs the probe under ``jax.eval_shape`` instead:
    ``variables`` come back as ShapeDtypeStructs and NOTHING is allocated —
    the sharded trainable uses this to derive partition-rule shardings
    BEFORE the real init, so an over-HBM flagship's params are born sharded
    (a concrete unsharded init would be the OOM).
    """
    rng = init_rngs or {
        "params": jax.random.key(0), "dropout": jax.random.key(1)
    }

    def run(f):
        if abstract:
            return jax.eval_shape(f, rng, sample_x)
        return jax.jit(f)(rng, sample_x)

    try:
        variables = run(lambda r, x: model.init(r, x, deterministic=True))
        return variables, "deterministic"
    except TypeError as exc:
        # Only a rejected 'deterministic' kwarg means "wrong convention".
        # Any other TypeError (e.g. a positional-encoding broadcast
        # mismatch when max_seq_length < the data's window length) is the
        # model's REAL failure: retrying with train= would just fail on
        # the unknown kwarg and mask the actual error behind a confusing
        # "unexpected keyword argument 'train'".  The match is deliberately
        # loose — any wording that names the flag as an argument problem
        # (CPython's current phrasing, a future rewording, a wrapper's
        # re-raise) counts — and a signature probe covers a TypeError that
        # names neither (a __call__ provably without the flag cannot have
        # run its body, so the error can only be the kwarg rejection).
        msg = str(exc)
        mentions_flag = "deterministic" in msg and (
            "argument" in msg or "keyword" in msg
        )
        if not mentions_flag and not _call_lacks_deterministic(model):
            raise
        variables = run(lambda r, x: model.init(r, x, train=False))
        return variables, "train"

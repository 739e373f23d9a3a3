"""Continual fine-tune: warm-start, short budget, cached program class.

The retrain leg of the self-healing loop (ISSUE 17 tentpole, part 2).
Deliberately NOT a ``tune.run`` sweep: the controller already knows the
architecture it is serving — what it needs is a few epochs of the SAME
training program over the recent (drifted) window, warm-started from the
newest committed generation, cheap enough to run inside a serving
process without claiming the fleet.

Zero new compiles on repeat retrains: the jitted epoch/eval programs are
cached module-wide, keyed by (architecture config, data shapes,
optimizer hyperparams).  A drifting stream retrains with the same config
and the same window shape every episode, so episode 2+ reuses episode
1's programs — ``program_cache_stats()["builds"]`` is the counter the
e2e asserts stops moving.  The program bodies are the shared ones from
``tune/_regression_program.py`` (same epoch scan, same forward
convention), so this is the training plane's compile-cache program
class, not a third training loop.

Chaos: the caller passes its fault plan and a ``trial_id``; every epoch
boundary consults ``plan.maybe_crash_trial`` — the mid-retrain crash
rides the SAME scheduled-fault machinery as sweep trials
(``InjectedTrialCrash``), and the controller's retry budget absorbs it.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional, Tuple

from distributed_machine_learning_tpu.analysis.locks import named_lock

_PROGRAMS: Dict[Any, Any] = {}
_PROGRAMS_LOCK = named_lock("loop.retrain.programs")
_PROGRAMS_MAX = 4
_stats = {"builds": 0, "hits": 0}


def program_cache_stats() -> Dict[str, int]:
    """Copy of the program-cache counters (builds == new trace+compile
    classes; a steady-state loop's builds counter is FLAT)."""
    with _PROGRAMS_LOCK:
        return dict(_stats)


def clear_program_cache() -> None:
    """Test hook: drop the cached programs and counters."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()
        _stats["builds"] = 0
        _stats["hits"] = 0


def _program_key(config, x_shape, y_shape, batch_size, lr) -> str:
    sig = {
        k: v for k, v in sorted(config.items())
        if isinstance(v, (str, int, float, bool, tuple, list, type(None)))
    }
    return json.dumps(
        [sig, list(x_shape), list(y_shape), int(batch_size), float(lr)],
        sort_keys=True, default=str,
    )


def _build_programs(config, sample_x, batch_size, n_train, lr):
    """Model + jitted epoch/eval programs for one retrain class."""
    import jax

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.ops.losses import get_loss
    from distributed_machine_learning_tpu.ops.optimizers import (
        make_optimizer,
    )
    from distributed_machine_learning_tpu.tune._regression_program import (
        detect_call_convention,
        make_epoch_fn,
        make_forward,
    )

    model = build_model(config)
    probe, flag_name = detect_call_convention(model, sample_x[:1])
    has_bn = "batch_stats" in probe
    forward = make_forward(model, flag_name, has_bn)
    loss_fn = get_loss(str(config.get("loss_function", "mse")))
    # Constant LR, no schedule: a short continual fine-tune has no warmup
    # phase to schedule, and baking the (fixed) LR keeps the program key
    # honest — change the knob, get a new class.
    tx = make_optimizer(
        str(config.get("optimizer", "adam")).lower(),
        learning_rate=lr,
        weight_decay=float(config.get("weight_decay", 0.0)),
        momentum=float(config.get("momentum", 0.0)),
        gradient_clipping=float(config.get("gradient_clipping", 0.0)),
    )
    num_batches = max(int(n_train) // int(batch_size), 1)
    epoch_fn = jax.jit(make_epoch_fn(
        forward, tx, loss_fn, int(n_train), num_batches, int(batch_size),
    ))

    def _eval(params, batch_stats, x, y):
        import jax.numpy as jnp

        preds, *_ = forward(params, batch_stats, x, None, train=False)
        preds = preds.astype(jnp.float32)
        return jnp.mean(
            jnp.abs(y - preds) / (jnp.abs(y) + 1e-8)
        )

    return {
        "model": model,
        "has_bn": has_bn,
        "init_opt": jax.jit(tx.init),
        "epoch": epoch_fn,
        "eval": jax.jit(_eval),
        "num_batches": num_batches,
    }


def _programs_for(config, x, y, batch_size, lr):
    key = _program_key(config, x.shape, y.shape, batch_size, lr)
    with _PROGRAMS_LOCK:
        progs = _PROGRAMS.get(key)
        if progs is not None:
            _stats["hits"] += 1
            return progs
    built = _build_programs(config, x, batch_size, x.shape[0], lr)
    from distributed_machine_learning_tpu import obs

    with _PROGRAMS_LOCK:
        progs = _PROGRAMS.get(key)
        if progs is None:
            _stats["builds"] += 1
            _PROGRAMS[key] = built
            while len(_PROGRAMS) > _PROGRAMS_MAX:
                _PROGRAMS.pop(next(iter(_PROGRAMS)))
            progs = built
        else:
            _stats["hits"] += 1
    obs.get_registry().add("loop_retrain_program_requests")
    return progs


def eval_mape(config, variables, x, y) -> float:
    """Holdout MAPE (fraction) of ``variables`` on ``(x, y)`` — the gate
    and probation comparisons both use this, so candidate and incumbent
    are judged by the same program."""
    import numpy as np

    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    progs = _programs_for(config, x, y, max(len(x), 1), 0.0)
    return float(progs["eval"](
        variables["params"], variables.get("batch_stats", {}), x, y
    ))


def fine_tune(
    config: Dict[str, Any],
    variables: Dict[str, Any],
    x,
    y,
    *,
    epochs: int = 6,
    learning_rate: float = 0.02,
    batch_size: int = 16,
    val_fraction: float = 0.25,
    seed: int = 0,
    trial_id: str = "loop-retrain",
    plan=None,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Short warm-start fine-tune of ``variables`` on the recent window.

    Returns ``(new_variables, info)``; ``info`` carries ``val_mape`` (on
    the held-back tail of the window), ``train_loss`` and the program-
    cache counters so callers can assert the zero-new-compiles property.
    Raises whatever the chaos plan schedules (``InjectedTrialCrash`` at
    an epoch boundary) — retry policy belongs to the controller.
    """
    import jax
    import numpy as np

    from distributed_machine_learning_tpu import obs

    x = np.asarray(x, dtype=np.float32)
    y = np.asarray(y, dtype=np.float32)
    if len(x) < 4:
        raise ValueError(f"retrain window too small: {len(x)} rows")
    n_val = max(int(len(x) * val_fraction), 1)
    x_train, y_train = x[:-n_val], y[:-n_val]
    x_val, y_val = x[-n_val:], y[-n_val:]
    batch_size = min(int(batch_size), len(x_train))

    builds_before = program_cache_stats()["builds"]
    with obs.span("loop.retrain", {
        "rows": int(len(x_train)), "epochs": int(epochs),
    }):
        progs = _programs_for(
            config, x_train, y_train, batch_size, learning_rate
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        opt_state = progs["init_opt"](params)
        import jax.numpy as jnp

        xd = jnp.asarray(x_train)
        yd = jnp.asarray(y_train)
        train_loss = None
        for e in range(int(epochs)):
            if plan is not None:
                plan.maybe_crash_trial(trial_id, e)
            params, opt_state, batch_stats, train_loss = progs["epoch"](
                params, opt_state, batch_stats, xd, yd,
                jax.random.PRNGKey(seed * 1000 + e),
            )
        val_mape = float(progs["eval"](
            params, batch_stats, jnp.asarray(x_val), jnp.asarray(y_val)
        ))
    new_vars: Dict[str, Any] = {"params": jax.device_get(params)}
    if progs["has_bn"] and batch_stats:
        new_vars["batch_stats"] = jax.device_get(batch_stats)
    stats = program_cache_stats()
    info = {
        "val_mape": val_mape,
        "train_loss": (
            float(train_loss) if train_loss is not None else None
        ),
        "epochs": int(epochs),
        "rows": int(len(x)),
        "program_builds": stats["builds"] - builds_before,
        "program_cache": stats,
    }
    return new_vars, info

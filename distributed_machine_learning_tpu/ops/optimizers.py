"""Optimizer registry.

Parity with the reference's name->class optimizer map (adam / adamw / sgd /
rmsprop, `/root/reference/ray-tune-hpo-regression.py:253-258, 290-296`), fixed
so that ``momentum`` is only forwarded to optimizers that accept it (the
reference passed it unconditionally and TypeError'd on Adam/AdamW — SURVEY.md
§2 C14).  Gradient clipping is composed here as an optax chain rather than an
imperative call (`:338-339`).
"""

from __future__ import annotations

from typing import Optional, Union

import optax

from distributed_machine_learning_tpu.utils.registry import Registry

optimizers: Registry = Registry("optimizer")

ScalarOrSchedule = Union[float, optax.Schedule]


@optimizers.register("adam")
def adam(learning_rate: ScalarOrSchedule, weight_decay: float = 0.0, **_):
    tx = optax.adam(learning_rate)
    if weight_decay:
        # Reference Adam ignores decoupled decay; emulate torch's L2-style
        # `weight_decay` by adding wd * p to the gradient before the update.
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


@optimizers.register("adamw")
def adamw(learning_rate: ScalarOrSchedule, weight_decay: float = 0.0, **_):
    return optax.adamw(learning_rate, weight_decay=weight_decay)


@optimizers.register("sgd")
def sgd(
    learning_rate: ScalarOrSchedule,
    weight_decay: float = 0.0,
    momentum: float = 0.0,
    **_,
):
    tx = optax.sgd(learning_rate, momentum=momentum or None)
    if weight_decay:
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


@optimizers.register("rmsprop")
def rmsprop(
    learning_rate: ScalarOrSchedule,
    weight_decay: float = 0.0,
    momentum: float = 0.0,
    **_,
):
    tx = optax.rmsprop(learning_rate, momentum=momentum)
    if weight_decay:
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


@optimizers.register("lamb")
def lamb(learning_rate: ScalarOrSchedule, weight_decay: float = 0.0, **_):
    return optax.lamb(learning_rate, weight_decay=weight_decay)


@optimizers.register("adafactor")
def adafactor(learning_rate: ScalarOrSchedule, weight_decay: float = 0.0, **_):
    return optax.adafactor(learning_rate, weight_decay_rate=weight_decay or None)


@optimizers.register("lion")
def lion(learning_rate: ScalarOrSchedule, weight_decay: float = 0.0, **_):
    # Sign-momentum optimizer: half the optimizer memory of Adam (one
    # moment), decoupled decay like adamw — a good fit for big-model
    # memory budgets on HBM-bound TPUs.
    return optax.lion(learning_rate, weight_decay=weight_decay)


def make_optimizer(
    name: str,
    learning_rate: ScalarOrSchedule,
    weight_decay: float = 0.0,
    momentum: float = 0.0,
    gradient_clipping: Optional[float] = None,
    accumulate_grad_batches: int = 1,
) -> optax.GradientTransformation:
    """Build an optax transformation from config values.

    ``gradient_clipping`` > 0 prepends global-norm clipping, matching the
    reference's ``clip_grad_norm_`` guard (`:338-339`).
    ``accumulate_grad_batches`` > 1 wraps the whole chain in
    ``optax.MultiSteps``: k micro-batch gradients average into one
    optimizer step — k× the effective batch without k× the activation
    memory (the standard big-model knob on HBM-bound TPUs). Clipping sits
    inside the wrapper, so it applies to the ACCUMULATED gradient, and the
    lr schedule advances once per real update, not per micro-batch.
    """
    tx = optimizers.get(name)(
        learning_rate, weight_decay=weight_decay, momentum=momentum
    )
    if gradient_clipping and gradient_clipping > 0:
        tx = optax.chain(optax.clip_by_global_norm(float(gradient_clipping)), tx)
    if accumulate_grad_batches and int(accumulate_grad_batches) > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=int(accumulate_grad_batches))
    return tx


# ---------------------------------------------------------------------------
# Injected-hyperparameter optimizers: lr/wd live in the optimizer STATE
# instead of being baked into the traced program as constants.  Two users:
# the vectorized runner (a population vmaps over the injected slots), and
# the per-trial trainable (every same-architecture trial then traces to
# IDENTICAL HLO, so the persistent XLA cache serves one compile to the
# whole cohort — per-trial backend compiles are otherwise the dominant
# cost of multi-trial runs of small models).

INJECTABLE_OPTIMIZERS = frozenset({"adam", "adamw", "sgd", "rmsprop"})


def make_injected_optimizer(
    name: str,
    shape_schedule,
    momentum: float = 0.0,
    gradient_clipping: float = 0.0,
) -> optax.GradientTransformation:
    """Optimizer whose lr/wd are *state* (``optax.inject_hyperparams``).

    The LR schedule contributes a shared *shape* (peak 1.0) via
    ``scale_by_schedule``; the injected per-run ``learning_rate`` scales it.
    Decay placement mirrors :func:`make_optimizer`'s registry semantics:
    L2-style (added to the gradient pre-update) for adam/sgd/rmsprop,
    decoupled (post-update) for adamw — the reference's optimizer-registry
    semantics (SURVEY.md §2 C14).  ``momentum`` and ``gradient_clipping``
    stay baked (they change the chain's structure).
    """
    name = name.lower()
    if name not in INJECTABLE_OPTIMIZERS:
        raise ValueError(
            f"injected mode supports {sorted(INJECTABLE_OPTIMIZERS)}, "
            f"got {name!r}"
        )

    def factory(learning_rate, weight_decay):
        parts, post = [], []
        if gradient_clipping and gradient_clipping > 0:
            parts.append(optax.clip_by_global_norm(float(gradient_clipping)))
        if name == "adam":
            parts.append(optax.add_decayed_weights(weight_decay))
            parts.append(optax.scale_by_adam())
        elif name == "adamw":
            parts.append(optax.scale_by_adam())
            parts.append(optax.add_decayed_weights(weight_decay))
        elif name == "sgd":
            parts.append(optax.add_decayed_weights(weight_decay))
            if momentum:
                # optax.sgd applies momentum BEFORE lr scaling.
                parts.append(optax.trace(decay=float(momentum)))
        elif name == "rmsprop":
            parts.append(optax.add_decayed_weights(weight_decay))
            parts.append(optax.scale_by_rms())
            if momentum:
                # optax.rmsprop applies momentum AFTER lr scaling — with a
                # non-constant schedule the orders genuinely differ (the
                # trace accumulates lr(t)-scaled steps), so placement must
                # match the registry's semantics exactly.
                post.append(optax.trace(decay=float(momentum)))
        parts.append(optax.scale_by_schedule(shape_schedule))
        parts.append(optax.scale(-1.0 * learning_rate))
        return optax.chain(*parts, *post)

    return optax.inject_hyperparams(factory)(learning_rate=0.0, weight_decay=0.0)


def set_injected_hyperparams(opt_state, lr, wd):
    """Return ``opt_state`` with lr/wd written into the inject slots."""
    import jax.numpy as jnp

    hp = dict(opt_state.hyperparams)
    hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
    hp["weight_decay"] = jnp.asarray(wd, jnp.float32)
    return opt_state._replace(hyperparams=hp)

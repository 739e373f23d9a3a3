"""Loss registry.

Parity with the reference's name->fn loss map (mse / mae / huber / mape,
`/root/reference/ray-tune-hpo-regression.py:313-319`) and its custom MAPE loss
(`:245-247`).  All losses are pure jax functions of ``(predictions, targets)``
returning a scalar, so they fuse into the jitted train step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from distributed_machine_learning_tpu.utils.registry import Registry

losses: Registry = Registry("loss")


@losses.register("mse")
def mse_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((predictions - targets) ** 2)


@losses.register("mae")
def mae_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(predictions - targets))


@losses.register("huber")
def huber_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    # delta=1.0 matches torch.nn.SmoothL1Loss defaults used by the reference.
    return jnp.mean(optax.huber_loss(predictions, targets, delta=1.0))


@losses.register("mape")
def mape_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean absolute percentage error ×100.

    The reference divides by the *signed* target (`:245-247`), which makes the
    training objective negative and unbounded below whenever targets < 0; we
    use |t| (the standard MAPE definition and the clear intent — its glucose
    targets are strictly positive, so the behaviors coincide on its data).
    """
    return jnp.mean(
        jnp.abs(targets - predictions) / (jnp.abs(targets) + 1e-8)
    ) * 100.0


@losses.register("rmse")
def rmse_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.mean((predictions - targets) ** 2))


# Losses whose targets are integer token ids and whose predictions are
# logits over a vocabulary: evaluation then reports the loss itself and
# not the regression errors (tune/_regression_program.py).
TOKEN_LOSSES = frozenset({"cross_entropy"})


def token_cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Per-position ``-log softmax(logits)[target]`` in float32: logits
    [..., V], integer targets [...]."""
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(
        logits, targets.astype(jnp.int32)[..., None], axis=-1
    )[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


# Positions of a sequence whose float32 logits are live at once in
# ``cross_entropy``: [B, 8192, V] logits are gigabytes in float32.
CROSS_ENTROPY_CHUNK = 1024


@losses.register("cross_entropy")
def cross_entropy_loss(predictions: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token cross-entropy: logits [B, S, V] against ids [B, S]
    (the data gives each position's target; nothing is shifted here).
    Logits come in the model's compute dtype and are widened here, a chunk
    of the sequence at a time, each chunk rematerialised in the backward
    pass."""
    with jax.named_scope("cross_entropy"):
        B, S = targets.shape
        chunk = CROSS_ENTROPY_CHUNK
        if S <= chunk or S % chunk:
            return jnp.mean(token_cross_entropy(predictions, targets))
        n = S // chunk
        logits = jnp.moveaxis(predictions.reshape(B, n, chunk, -1), 1, 0)
        ids = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
        sums = jax.lax.map(
            jax.checkpoint(lambda xs: jnp.sum(token_cross_entropy(*xs))),
            (logits, ids),
        )
        return jnp.sum(sums) / (B * S)


# The training step widens predictions to float32 before a loss sees them,
# except for a loss that says it does so itself.
cross_entropy_loss.widens_itself = True


def get_loss(name: str):
    return losses.get(name)

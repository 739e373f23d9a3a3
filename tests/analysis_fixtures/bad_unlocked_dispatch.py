"""Historical bug (utils/dispatch.py): concurrent trial threads
dispatching device work outside dispatch_lock — key creation, schedule evaluation, and the epoch program
itself must all ride inside the hold."""

import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.utils.dispatch import dispatch_lock


def epoch_body(params, lr, shape_schedule, step):
    epoch_key = jax.random.key(step)  # EXPECT: unlocked-dispatch
    lr_now = lr * float(shape_schedule(step))  # EXPECT: unlocked-dispatch
    with dispatch_lock():
        out = jnp.dot(params, params)
    loss = jnp.sum(out)  # EXPECT: unlocked-dispatch
    return epoch_key, lr_now, loss


def legacy_restore(tx, params):
    opt_state = jax.jit(tx.init)(params)  # EXPECT: unlocked-dispatch
    return opt_state

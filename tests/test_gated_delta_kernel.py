"""The gated delta rule's Pallas kernels (``ops/pallas_gated_delta.py``),
run in the Pallas interpreter on the CPU at head sizes of 128 and the
chunk of 64: the value and all five gradients against XLA's chunked
program (``ops/gated_delta.py _chunked``) and against the reference's
position-by-position recurrence; which path a call takes; and the epoch
programs of the two families that import ``models/hybrid_lm.py`` without
calling the rule, as the parent lowered them."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import gated_hybrid_lm as ref
from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.ops import gated_delta
from distributed_machine_learning_tpu.ops import pallas_gated_delta
from distributed_machine_learning_tpu.ops.losses import get_loss

D = 128

# name: (S, key heads, value heads, g drawn from -[lo, hi), beta from
# [lo, hi), matmul dtype, under jax.checkpoint)
CASES = {
    "one_block": (128, 1, 1, (0.0, 1.5), (0.0, 1.0), jnp.float32, False),
    "padded_last_chunk": (100, 1, 1, (0.0, 1.5), (0.0, 1.0), jnp.float32,
                          False),
    "two_blocks_of_eight_chunks": (1024, 1, 1, (0.0, 0.2), (0.0, 1.0),
                                   jnp.float32, False),
    "two_key_heads_serve_four": (192, 2, 4, (0.0, 1.5), (0.0, 1.0),
                                 jnp.float32, False),
    "decay_underflows": (128, 1, 2, (20.0, 80.0), (0.0, 1.0), jnp.float32,
                         False),
    "beta_near_0": (128, 1, 1, (0.0, 1.5), (0.0, 1e-3), jnp.float32, False),
    "beta_near_1": (128, 1, 1, (0.0, 0.05), (1.0 - 1e-3, 1.0), jnp.float32,
                    False),
    "bfloat16_products": (192, 1, 2, (0.0, 1.5), (0.0, 1.0), jnp.bfloat16,
                          False),
    "checkpointed": (130, 1, 2, (0.0, 1.5), (0.0, 1.0), jnp.float32, True),
}


def _inputs(seed, S, hk, hv, g_range, beta_range):
    ks = jax.random.split(jax.random.key(seed), 6)
    k = jax.random.normal(ks[1], (1, S, hk, D)) + 0.3
    return (
        jax.random.normal(ks[0], (1, S, hk, D)) * D ** -0.5,
        k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        jax.random.normal(ks[2], (1, S, hv, D)),
        -jax.random.uniform(ks[3], (1, S, hv), minval=g_range[0],
                            maxval=g_range[1]),
        jax.random.uniform(ks[4], (1, S, hv), minval=beta_range[0],
                           maxval=beta_range[1]),
        jax.random.normal(ks[5], (1, S, hv, D)),
    )


def _grouped(rule):
    """A rule of q, k at the value heads, called with q, k at key heads."""
    def call(q, k, v, g, beta):
        r = v.shape[2] // q.shape[2]
        return rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                    v, g, beta)

    return call


def _value_and_grads(rule, args, probe, remat=False):
    def probed(*a):
        out = rule(*a)
        return jnp.sum(out * probe), out

    fn = jax.checkpoint(probed) if remat else probed
    (_, out), grads = jax.jit(jax.value_and_grad(
        fn, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return [np.asarray(a, np.float32) for a in (out, *grads)]


def _gaps(got, want):
    """Largest difference of each of o, dq, dk, dv, dg, dbeta over the
    largest entry of ``want``'s, or over 1e-3 where all are smaller: where
    the decay underflows, dg is a sum of terms near 1e-9 that cancel, in
    the chunked form (XLA's and the kernels') as in no other."""
    return [float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-3))
            for a, b in zip(got, want)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_match_xla_and_the_recurrence(case):
    S, hk, hv, g_range, beta_range, dtype, remat = CASES[case]
    *args, probe = _inputs(len(case), S, hk, hv, g_range, beta_range)
    assert pallas_gated_delta.fits(64, D, D, hk, hv)
    got = _value_and_grads(
        lambda *a: gated_delta.chunk_gated_delta_rule(*a, matmul_dtype=dtype),
        args, probe, remat)
    assert all(np.isfinite(a).all() for a in got)
    xla = _value_and_grads(_grouped(
        lambda *a: gated_delta._chunked(*a, 64, dtype)), args, probe)
    if dtype == jnp.float32:
        truth = _value_and_grads(_grouped(
            lambda *a: ref.gated_delta_recurrence(*a, block=16)), args, probe)
        # The same sums in another order, float32 throughout.
        assert max(_gaps(got, xla)) < 2e-5, _gaps(got, xla)
        assert max(_gaps(got, truth)) < 1e-4, _gaps(got, truth)
        return
    # bfloat16 operands: as near to the float32 program as XLA's own
    # bfloat16 program is, output and each gradient.
    truth = _value_and_grads(_grouped(
        lambda *a: gated_delta._chunked(*a, 64, jnp.float32)), args, probe)
    for ours, theirs in zip(_gaps(got, truth), _gaps(xla, truth)):
        assert ours < 1.5 * theirs + 1e-6 and ours < 2e-2


def test_the_path_is_chosen_by_the_shapes():
    """Head sizes of 128 and the chunk of 64 take the kernels; the tiny
    widths of the family's other tests, or another chunk, XLA's program."""
    registry = obs.get_registry()

    def calls(q_shape, v_shape, chunk=64):
        before = [registry.get("gated_delta." + n)
                  for n in ("kernel_calls", "xla_calls")]
        S, hv = v_shape[1], v_shape[2]
        jax.eval_shape(
            lambda *a: gated_delta.chunk_gated_delta_rule(*a, chunk=chunk),
            jax.ShapeDtypeStruct(q_shape, jnp.float32),
            jax.ShapeDtypeStruct(q_shape, jnp.float32),
            jax.ShapeDtypeStruct(v_shape, jnp.float32),
            jax.ShapeDtypeStruct((1, S, hv), jnp.float32),
            jax.ShapeDtypeStruct((1, S, hv), jnp.float32),
        )
        return [registry.get("gated_delta." + n) - b
                for n, b in zip(("kernel_calls", "xla_calls"), before)]

    assert calls((1, 96, 2, 128), (1, 96, 4, 128)) == [1, 0]
    assert calls((1, 96, 2, 256), (1, 96, 4, 128)) == [1, 0]
    assert calls((1, 21, 2, 16), (1, 21, 4, 16), chunk=8) == [0, 1]
    assert calls((1, 96, 2, 128), (1, 96, 4, 128), chunk=32) == [0, 1]
    assert calls((1, 96, 2, 64), (1, 96, 4, 128)) == [0, 1]


@pytest.mark.parametrize("common", [0.0, 1.0, 2.0])
def test_the_solve_is_a_float32_substitution(common):
    """``T = (I + A)^{-1}`` for unit keys with a ``common`` component and
    little decay, within float32's reach of the float64 inverse: at 2 a
    product form of the inverse, ``(I - A)(I + A^2)...``, loses every
    digit."""
    rng = np.random.default_rng(0)
    k = rng.normal(size=(64, D)) + common
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    a = np.tril((k * rng.uniform(0.3, 1.0, size=(64, 1))) @ k.T, -1)
    want = np.linalg.inv(np.eye(64) + a)
    got = jax.jit(pallas_gated_delta._inverse)(jnp.asarray(a, jnp.float32))
    assert np.max(np.abs(np.asarray(got, np.float64) - want)) < 1e-6


# The epoch programs of the families that import models/hybrid_lm.py
# (``DroplessMoE``) and never call the rule, at their tests' tiny sizes,
# lowered on a CPU (jax 0.9.0; ``as_text()`` of ``make_epoch_fn`` under
# jit, shapes only, no source locations) from the parent tree, whose
# mixer repeated q and k to the value heads itself.
PARENT_EPOCH_SHA256 = {
    "cca_moe_lm":
        "615794e2a5668db090cceabf0b6bb3376966777b0d8016856b75f9e597e2c4f1",
    "swa_moe_lm":
        "a9662e43bc3a97c98c5d59b5ae3c08427ee0288256cf2e910a415b2e1d20446c",
}


@pytest.mark.parametrize("family", sorted(PARENT_EPOCH_SHA256))
def test_the_families_beside_the_rule_lower_as_the_parents(family):
    from test_cca_moe_lm import TRIAL as CCA_TRIAL
    from test_swa_moe_lm import TRIAL as SWA_TRIAL

    from distributed_machine_learning_tpu.tune._regression_program import (
        make_epoch_fn,
    )

    model = build_model({"cca_moe_lm": CCA_TRIAL,
                         "swa_moe_lm": SWA_TRIAL}[family])
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    params = jax.eval_shape(
        lambda t: model.init({"params": jax.random.key(0)}, t[:1])["params"],
        tokens)
    tx = optax.adam(1e-3)

    def forward(p, batch_stats, x, key, train=True):
        return model.apply({"params": p}, x), batch_stats, 0.0

    epoch = make_epoch_fn(forward, tx, get_loss("cross_entropy"), 4, 2, 2)
    text = jax.jit(epoch).lower(
        params, jax.eval_shape(tx.init, params), {}, tokens, tokens,
        jax.random.key(0)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_EPOCH_SHA256[family]

"""Injected-hyperparameter optimizers (ops/optimizers.py): lr/wd as state.

The point: every same-architecture trial traces to IDENTICAL HLO, so the
whole cohort shares ONE backend compile (per-trial compiles are otherwise
the dominant cost of thread-executor HPO on small models).  Covers: program sharing across lr/wd,
numeric equivalence with the baked registry path, and the trainable's
restore override (PBT explore must win over a restored peer's slots).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.ops.optimizers import (
    INJECTABLE_OPTIMIZERS,
    make_injected_optimizer,
    make_optimizer,
    set_injected_hyperparams,
)
from distributed_machine_learning_tpu.ops.schedules import get_schedule


def _params():
    return {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}


def _grads():
    return {"w": jnp.full((4, 4), 0.5), "b": jnp.full((4,), -0.25)}


def test_one_compile_serves_every_lr_wd():
    """Different lr/wd hit the SAME jitted executable (lr/wd are state,
    not constants) — the property the cohort-sharing design rests on."""
    shape = get_schedule("constant", learning_rate=1.0)
    tx = make_injected_optimizer("adam", shape)
    params = _params()

    @jax.jit
    def update(grads, opt_state, params):
        return tx.update(grads, opt_state, params)

    outs = []
    for lr, wd in ((1e-3, 0.0), (5e-2, 1e-4), (1e-4, 1e-2)):
        st = set_injected_hyperparams(tx.init(params), lr, wd)
        updates, _ = update(_grads(), st, params)
        outs.append(updates["w"][0, 0])
    assert update._cache_size() == 1  # one traced program served all three
    assert len({float(o) for o in outs}) == 3  # and they really differ


@pytest.mark.parametrize("name", sorted(INJECTABLE_OPTIMIZERS))
def test_injected_matches_baked_registry_updates(name):
    """Injected chain == the registry's baked chain, step for step, for
    every supported optimizer (decay placement included)."""
    lr, wd, steps = 3e-3, 1e-3, 4
    sched = get_schedule("warmup_linear_decay", learning_rate=lr,
                         warmup_steps=2, total_steps=steps)
    shape = get_schedule("warmup_linear_decay", learning_rate=1.0,
                         warmup_steps=2, total_steps=steps)
    baked = make_optimizer(name, learning_rate=sched, weight_decay=wd,
                           momentum=0.9 if name in ("sgd", "rmsprop")
                           else 0.0, gradient_clipping=0.1)
    inj = make_injected_optimizer(name, shape,
                                  momentum=0.9 if name in ("sgd", "rmsprop")
                                  else 0.0, gradient_clipping=0.1)
    p_b = p_i = _params()
    s_b = baked.init(p_b)
    s_i = set_injected_hyperparams(inj.init(p_i), lr, wd)
    import optax

    for _ in range(steps):
        u_b, s_b = baked.update(_grads(), s_b, p_b)
        u_i, s_i = inj.update(_grads(), s_i, p_i)
        p_b = optax.apply_updates(p_b, u_b)
        p_i = optax.apply_updates(p_i, u_i)
    np.testing.assert_allclose(np.asarray(p_b["w"]), np.asarray(p_i["w"]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p_b["b"]), np.asarray(p_i["b"]),
                               rtol=1e-5, atol=1e-7)


def _tiny_data():
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8, 4).astype(np.float32)
    y = rng.randn(64, 1).astype(np.float32)
    return Dataset(x[:48], y[:48]), Dataset(x[48:], y[48:])


def test_trainable_injected_and_baked_paths_agree():
    """train_regressor's injected default reproduces the legacy baked
    path's trajectory (same config, same seed) to float tolerance."""
    from distributed_machine_learning_tpu import tune

    train, val = _tiny_data()
    base = {
        "model": "mlp", "hidden_sizes": (8,), "learning_rate": 5e-3,
        "weight_decay": 1e-4, "num_epochs": 3, "batch_size": 16,
        "optimizer": "adamw", "seed": 7, "lr_schedule": "constant",
    }
    results = {}
    for tag, inject in (("injected", True), ("baked", False)):
        seen = []
        with tune.standalone():
            import distributed_machine_learning_tpu.tune.session as sess

            orig_report = sess._get_session().report
            sess._get_session().report = (
                lambda m, c=None: seen.append(dict(m))
            )
            try:
                tune.train_regressor(
                    dict(base, inject_hyperparams=inject),
                    train_data=train, val_data=val,
                )
            finally:
                sess._get_session().report = orig_report
        results[tag] = [m["validation_loss"] for m in seen]
    assert len(results["injected"]) == 3
    np.testing.assert_allclose(results["injected"], results["baked"],
                               rtol=1e-4)


INPUT_MODES = ("resident", "streaming")


def _run_capturing(config, train, val, checkpoint=None):
    """``train_regressor`` under a bare session: its records and its
    checkpoints, the latter copied to the host as they are reported (the
    next epoch's donated buffers reuse those arrays; the real executor's
    writer copies too)."""
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import session as sess_mod

    records, checkpoints = [], []

    def report(metrics, ckpt=None):
        records.append(dict(metrics))
        if ckpt is not None:
            checkpoints.append(jax.tree.map(
                lambda a: np.asarray(a) if isinstance(a, jax.Array) else a,
                ckpt))
        return "continue"

    sess_mod.set_session(sess_mod.Session(
        trial=sess_mod._StandaloneTrial(), report_fn=report,
        checkpoint_loader=lambda: checkpoint))
    try:
        tune.train_regressor(config, train_data=train, val_data=val)
    finally:
        sess_mod.set_session(None)
    return records, checkpoints


def _as_stored(checkpoint):
    """Through the real serialization: production checkpoints arrive as
    msgpack state-dicts, not live pytrees."""
    import tempfile

    from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib

    with tempfile.TemporaryDirectory() as d:
        path = ckpt_lib.save_checkpoint(d + "/ckpt.msgpack", checkpoint)
        return ckpt_lib.load_checkpoint(path)


@pytest.mark.parametrize("input_mode", INPUT_MODES)
def test_restore_overrides_hyperparams_from_config(input_mode):
    """A restored opt_state (e.g. a PBT peer's) must adopt THIS config's
    lr/wd — set_injected_hyperparams over the restored slots."""
    train, val = _tiny_data()
    base = {
        "model": "mlp", "hidden_sizes": (8,), "learning_rate": 1e-3,
        "num_epochs": 1, "batch_size": 16, "optimizer": "adamw",
        "seed": 3, "lr_schedule": "constant", "input_mode": input_mode,
    }
    _, peer = _run_capturing(base, train, val)
    slots = peer[-1]["opt_state"].hyperparams
    assert float(slots["learning_rate"]) == pytest.approx(1e-3)
    explored = dict(base, learning_rate=2e-2, weight_decay=3e-4,
                    num_epochs=2)  # explore perturbed
    records, mine = _run_capturing(
        explored, train, val, checkpoint=_as_stored(peer[-1])
    )
    assert [r["epoch"] for r in records] == [1]  # resumed after the peer's
    slots = mine[-1]["opt_state"].hyperparams
    assert float(slots["learning_rate"]) == pytest.approx(2e-2)
    assert float(slots["weight_decay"]) == pytest.approx(3e-4)


@pytest.mark.parametrize("input_mode", INPUT_MODES)
def test_legacy_baked_checkpoint_restores_under_injected_default(input_mode):
    """A checkpoint written by the pre-injection (baked) optimizer layout
    must still restore: the trainable detects the pytree mismatch and
    falls back to the baked chain for that incarnation (review r5)."""
    train, val = _tiny_data()
    base = {
        "model": "mlp", "hidden_sizes": (8,), "learning_rate": 5e-3,
        "num_epochs": 2, "batch_size": 16, "optimizer": "adam",
        "seed": 3, "lr_schedule": "constant", "input_mode": input_mode,
    }
    # 1) Produce a BAKED-layout checkpoint (inject disabled).
    _, baked = _run_capturing(
        dict(base, inject_hyperparams=False), train, val
    )
    # 2) Resume under the injected DEFAULT: must not raise, must continue
    # from the stored epoch (exactly one more epoch of reports), and on
    # the baked chain, whose state is what the next checkpoint carries.
    seen, after = _run_capturing(
        dict(base), train, val, checkpoint=_as_stored(baked[0])
    )
    assert len(seen) == 1  # resumed at epoch 2 of 2
    assert np.isfinite(seen[0]["validation_loss"])
    assert not hasattr(after[-1]["opt_state"], "hyperparams")


@pytest.mark.parametrize("name,injected", [
    ("adam", True), ("sgd", True), ("lamb", False),
])
def test_build_optimizer_is_the_chain_each_trainable_built(name, injected):
    """The one builder against the registry called as the three trainables
    used to call it: the same state tree from ``init`` and the same first
    update."""
    from distributed_machine_learning_tpu.tune.trainable import (
        build_optimizer,
        trial_settings,
    )

    config = {
        "optimizer": name, "learning_rate": 3e-3, "weight_decay": 1e-3,
        "momentum": 0.9, "gradient_clipping": 0.1, "warmup_steps": 2,
    }
    settings = trial_settings(config)
    assert settings.injected == injected
    tx, shape = build_optimizer(settings, 8, settings.injected)

    def schedule(peak):
        return get_schedule("warmup_linear_decay", learning_rate=peak,
                            warmup_steps=2, total_steps=8)

    if injected:
        old = make_injected_optimizer(
            name, schedule(1.0), momentum=0.9, gradient_clipping=0.1
        )
    else:
        old = make_optimizer(
            name, learning_rate=schedule(3e-3), weight_decay=1e-3,
            momentum=0.9, gradient_clipping=0.1, accumulate_grad_batches=1,
        )
    assert float(shape(1)) == float(schedule(1.0)(1))
    states = [t.init(_params()) for t in (tx, old)]
    if injected:
        states = [set_injected_hyperparams(s, 3e-3, 1e-3) for s in states]
    assert jax.tree.structure(states[0]) == jax.tree.structure(states[1])
    updates = [
        t.update(_grads(), s, _params()) for t, s in zip((tx, old), states)
    ]
    for a, b in zip(jax.tree.leaves(updates[0]), jax.tree.leaves(updates[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trial_seed_varies_init_weights():
    """The trial seed must produce DISTINCT initial weights (r5: a fixed
    init key made every thread-executor trial start from identical
    params — the reference's torch trials each get their own random
    init, and the vectorized runner seeds per-row).  Same seed stays
    bit-reproducible."""
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import session as sess_mod

    train, val = _tiny_data()

    def first_val_loss(seed):
        seen = []
        sess_mod.set_session(sess_mod.Session(
            trial=None,
            report_fn=lambda m, c=None: (seen.append(dict(m)),
                                         "continue")[1],
            checkpoint_loader=lambda: None))
        try:
            tune.train_regressor(
                {"model": "mlp", "hidden_sizes": (8,),
                 "learning_rate": 1e-9,  # ~frozen: loss reflects the init
                 "num_epochs": 1, "batch_size": 16, "seed": seed,
                 "lr_schedule": "constant"},
                train_data=train, val_data=val)
        finally:
            sess_mod.set_session(None)
        return seen[0]["validation_loss"]

    a, b, a2 = first_val_loss(1), first_val_loss(2), first_val_loss(1)
    assert a == a2  # deterministic in the seed
    assert a != b   # distinct inits across seeds


def test_cohort_program_cache_builds_once_per_architecture():
    """tune.run cohort sharing: trials of one architecture stage data and
    build programs ONCE (per-trial seeds still produce distinct inits);
    a different architecture or changed data rebuilds; clear() frees."""
    import distributed_machine_learning_tpu.tune.trainable as tr
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.tune import session as sess_mod

    train, val = _tiny_data()
    tr.clear_cohort_program_cache()
    builds = []
    orig = tr.build_model

    def counting_build(cfg):
        builds.append(1)
        return orig(cfg)

    tr.build_model = counting_build
    try:
        losses = []
        for seed in (1, 2, 3):
            seen = []
            sess_mod.set_session(sess_mod.Session(
                trial=None,
                report_fn=lambda m, c=None: (seen.append(dict(m)),
                                             "continue")[1],
                checkpoint_loader=lambda: None))
            try:
                tune.train_regressor(
                    {"model": "mlp", "hidden_sizes": (8,),
                     "learning_rate": 1e-9, "num_epochs": 1,
                     "batch_size": 16, "seed": seed,
                     "lr_schedule": "constant"},
                    train_data=train, val_data=val)
            finally:
                sess_mod.set_session(None)
            losses.append(seen[0]["validation_loss"])
        assert len(builds) == 1  # one build served all three trials
        assert len(set(losses)) == 3  # ...with distinct per-seed inits
        # A different architecture is a different cohort.
        sess_mod.set_session(sess_mod.Session(
            trial=None, report_fn=lambda m, c=None: "continue",
            checkpoint_loader=lambda: None))
        try:
            tune.train_regressor(
                {"model": "mlp", "hidden_sizes": (16,),
                 "learning_rate": 1e-3, "num_epochs": 1, "batch_size": 16,
                 "lr_schedule": "constant"},
                train_data=train, val_data=val)
        finally:
            sess_mod.set_session(None)
        assert len(builds) == 2
        # In-place data mutation changes the key (checksums): rebuild.
        train.y[:] = train.y + 1.0
        sess_mod.set_session(sess_mod.Session(
            trial=None, report_fn=lambda m, c=None: "continue",
            checkpoint_loader=lambda: None))
        try:
            tune.train_regressor(
                {"model": "mlp", "hidden_sizes": (8,),
                 "learning_rate": 1e-3, "num_epochs": 1, "batch_size": 16,
                 "seed": 9, "lr_schedule": "constant"},
                train_data=train, val_data=val)
        finally:
            sess_mod.set_session(None)
        assert len(builds) == 3
    finally:
        tr.build_model = orig
        tr.clear_cohort_program_cache()

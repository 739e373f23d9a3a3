#!/usr/bin/env python3
"""Chip smoke: the trainer, the sweep and the server, once, on a TPU.

    python chip_smoke.py             # one chip: train, sweep, cohort, serve
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One process (a chip belongs to one process at a time), phases in sequence,
through the entry points a user calls (``tune.run``, ``tune.run_vectorized``,
``serve.export_bundle`` / ``PredictionServer``).  There is no CPU mode: the
script refuses to run when ``jax.devices()[0].platform`` is not ``"tpu"``.
Any failed check raises, so a failed phase never ends in exit 0; every
phase runs under a time limit that ends the process.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
versions, the cache directory, compile seconds, step times and counters go
on earlier lines.

The phases are functions that take their sizes, so tests/test_chip_smoke.py
calls them tiny on the CPU; ``main()`` owns the real sizes and the checks
that only a TPU can satisfy.
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

# bf16 inputs, f32 accumulation on both sides: the kernel and XLA's fused
# attention differ by rounding order only.  Normalized max error (below).
ATTENTION_TOL = 5e-2
# Same config and seed on different meshes: reduction order differs.
MESH_LOSS_RTOL = 5e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str, limit_s: float):
    """Run a phase under a hard time limit.  Device calls block in native
    code where no signal lands, so the limit is a watchdog thread that
    dumps every stack and ends the process."""

    def expire():
        sys.stderr.write(
            f"[chip_smoke] phase {name!r} passed its {limit_s:.0f}s limit\n"
        )
        faulthandler.dump_traceback(file=sys.stderr)
        sys.stderr.flush()
        os._exit(2)

    timer = threading.Timer(limit_s, expire)
    timer.daemon = True
    say(f"phase {name}: start (limit {limit_s:.0f}s)")
    t0 = time.time()
    timer.start()
    try:
        yield
    finally:
        timer.cancel()
    say(f"phase {name}: ok in {time.time() - t0:.1f}s")


def normalized_max_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-12))


def random_qkvw(shape, seed: int):
    """bf16 q, k, v and a weight tensor (a non-trivial cotangent)."""
    import jax
    import jax.numpy as jnp

    return tuple(
        jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
        for kk in jax.random.split(jax.random.key(seed), 4)
    )


def out_and_grads(fn, w, q, k, v):
    """``fn(q, k, v)`` and the gradients of its ``w``-weighted sum.  ``w``
    is an ARGUMENT of the jitted loss: closed over, it is baked into the
    executable as a constant the size of q (a 71 MB cache entry at the
    ``train`` shape, enough to thrash a size-capped compile cache)."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v, w):
        return (fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)).sum()

    return jax.jit(fn)(q, k, v), jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    )(q, k, v, w)


def attention_errors(got, want, tol, what):
    """Normalized max error of (out, (dq, dk, dv)) pairs; raises past tol."""
    (got_out, got_grads), (want_out, want_grads) = got, want
    errs = {"out": normalized_max_err(got_out, want_out)}
    for name, g, r in zip(("dq", "dk", "dv"), got_grads, want_grads):
        errs[name] = normalized_max_err(g, r)
    assert all(e == e and e <= tol for e in errs.values()), (
        f"{what} beyond {tol}: {errs}"
    )
    return errs


def sharded_live_arrays(min_devices: int):
    """Evidence of where sharded state really lies: every live jax array
    that is split (not replicated) over >= ``min_devices`` devices, as
    (shape, spec, sorted device ids of its addressable shards)."""
    import jax

    out = []
    for a in jax.live_arrays():
        sharding = a.sharding
        if len(sharding.device_set) < min_devices:
            continue
        if sharding.is_fully_replicated:
            continue
        ids = sorted({s.device.id for s in a.addressable_shards})
        out.append((tuple(a.shape), str(getattr(sharding, "spec", "")), ids))
    return out


# ---------------------------------------------------------------------------
# One chip


def flash_vs_dense(batch, seq_len, heads, head_dim, *, interpret, tol):
    """flash_attention against XLA's dot_product_attention on one layer's
    q/k/v: output and all three gradients."""
    from distributed_machine_learning_tpu.ops.attention import (
        dot_product_attention,
    )
    from distributed_machine_learning_tpu.ops.pallas_attention import (
        flash_attention,
    )

    q, k, v, w = random_qkvw((batch, seq_len, heads, head_dim), seed=0)
    flash = lambda q, k, v: flash_attention(q, k, v, interpret=interpret)
    return attention_errors(
        out_and_grads(flash, w, q, k, v),
        out_and_grads(dot_product_attention, w, q, k, v),
        tol, "flash vs dot_product_attention",
    )


def pallas_calls_in_epoch_program(config) -> int:
    """Count ``tpu_custom_call`` in the text of THE epoch program the last
    ``train_regressor`` cohort ran: its cached jitted function, lowered on
    abstract arguments.  The lowered text, not a second compile: compiling
    it again here took ~65 s and wrote a second ~50 MB cache entry per
    attention type (the jit call and ``.lower().compile()`` do not share a
    persistent-cache key), which alone pushed one run past a 192 MiB cache
    cap.  A custom call whose results feed the loss is not something the
    compiler can drop."""
    import jax

    from distributed_machine_learning_tpu.ops.rng import resolve_rng_impl
    from distributed_machine_learning_tpu.tune import trainable
    from distributed_machine_learning_tpu.utils.seeding import init_rngs_for

    (bundle,) = trainable._COHORT_CACHE.values()
    data = bundle.data
    variables = jax.eval_shape(
        bundle.init_model, init_rngs_for(0), data.x_train[:1]
    )
    params = variables["params"]
    opt_state = jax.eval_shape(bundle.init_opt, params)
    key = jax.eval_shape(
        lambda: jax.random.key(0, impl=resolve_rng_impl(config))
    )
    lowered = bundle.train_epoch.lower(
        params, opt_state, variables.get("batch_stats", {}),
        data.x_train, data.y_train, key,
    )
    return lowered.as_text().count("tpu_custom_call")


def phase_train(*, d_model, num_heads, dim_feedforward, num_layers, seq_len,
                features, batch_size, n_train, n_val, num_epochs, storage,
                interpret=False, seed=0):
    """The trainer at one fixed config, once with the flash kernel asked
    for by name and once with plain softmax: finite losses, a checkpoint
    written and restored, and the kernel against XLA on one layer."""
    import numpy as np

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import dummy_regression_data
    from distributed_machine_learning_tpu.tune.trainable import (
        clear_cohort_program_cache,
    )
    from distributed_machine_learning_tpu.tune.trial import TrialStatus

    train, val = dummy_regression_data(
        num_samples=n_train + n_val, seq_len=seq_len, num_features=features,
        val_fraction=n_val / (n_train + n_val), seed=seed,
    )
    out = {}
    for attention_type in ("flash", "scaled_dot_product"):
        config = {
            "model": "transformer", "d_model": d_model,
            "num_heads": num_heads, "dim_feedforward": dim_feedforward,
            "num_layers": num_layers, "max_seq_length": seq_len,
            "attention_type": attention_type, "compute_dtype": "bfloat16",
            "dropout": 0.1, "learning_rate": 1e-4, "weight_decay": 1e-4,
            "batch_size": batch_size, "num_epochs": num_epochs,
            "seed": seed,
        }
        clear_cohort_program_cache()
        t0 = time.time()
        analysis = tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            config, metric="validation_loss", mode="min", num_samples=1,
            storage_path=storage, name=f"train_{attention_type}", verbose=0,
        )
        wall = time.time() - t0
        trial = analysis.trials[0]
        assert trial.status == TrialStatus.TERMINATED, (
            attention_type, trial.status, trial.error
        )
        assert trial.training_iteration == num_epochs, trial.results
        losses = [
            (r["train_loss"], r["validation_loss"]) for r in trial.results
        ]
        assert np.isfinite(losses).all(), losses
        # The checkpoint each epoch wrote, read back into a fresh model.
        assert trial.latest_checkpoint, "no checkpoint written"
        model, variables = analysis.best_model()
        preds = np.asarray(
            model.apply(variables, np.asarray(val.x[:2]), deterministic=True),
            np.float32,
        )
        assert preds.shape == (2, 1) and np.isfinite(preds).all(), preds
        last = trial.results[-1]
        out[attention_type] = {
            "losses": losses,
            "wall_s": round(wall, 1),
            "compile_s": last.get("compile_time_s"),
            "epoch_time_s": last.get("epoch_time_s"),
            "step_s": round(
                last["epoch_time_s"] / max(n_train // batch_size, 1), 4
            ),
            "device_bytes_in_use": last.get("device_bytes_in_use"),
            "pallas_calls": pallas_calls_in_epoch_program(config),
        }
        say(f"train[{attention_type}]: {json.dumps(out[attention_type])}")
    out["flash_vs_dense"] = flash_vs_dense(
        batch_size, seq_len, num_heads, d_model // num_heads,
        interpret=interpret, tol=ATTENTION_TOL,
    )
    say(f"train: flash vs dot_product_attention (normalized max err, "
        f"tol {ATTENTION_TOL}): {json.dumps(out['flash_vs_dense'])}")
    return out


def sweep_space(*, d_model, num_heads, num_layers, dim_feedforward, seq_len,
                batch_size, num_epochs):
    from distributed_machine_learning_tpu import tune

    return {
        "model": "transformer", "d_model": d_model, "num_heads": num_heads,
        "num_layers": num_layers, "dim_feedforward": dim_feedforward,
        "dropout": 0.1, "max_seq_length": seq_len,
        "learning_rate": tune.loguniform(1e-4, 1e-2),
        "weight_decay": tune.loguniform(1e-6, 1e-3),
        "seed": tune.randint(0, 1_000_000),
        "num_epochs": num_epochs, "batch_size": batch_size,
        "loss_function": "mse",
    }


def run_sweep(space, train, val, *, num_trials, storage, name, devices=None,
              callbacks=None):
    from distributed_machine_learning_tpu import tune

    t0 = time.time()
    analysis = tune.run_vectorized(
        space, train_data=train, val_data=val, metric="validation_loss",
        mode="min", num_samples=num_trials, max_batch_trials=num_trials,
        scheduler=tune.ASHAScheduler(
            max_t=space["num_epochs"], grace_period=1, reduction_factor=2
        ),
        storage_path=storage, name=name, seed=42, verbose=0,
        devices=devices, callbacks=callbacks,
    )
    with open(os.path.join(analysis.root, "experiment_state.json")) as f:
        state = json.load(f)
    return analysis, state, time.time() - t0


def phase_sweep(*, d_model, num_heads, num_layers, dim_feedforward, seq_len,
                features, batch_size, num_trials, num_epochs, data_steps,
                storage):
    """The vmapped population sweep under ASHA on the reference's shape."""
    import numpy as np

    from distributed_machine_learning_tpu.data import glucose_like_data

    train, val = glucose_like_data(
        num_steps=data_steps, num_features=features, interval=seq_len,
        stride=seq_len,
    )
    space = sweep_space(
        d_model=d_model, num_heads=num_heads, num_layers=num_layers,
        dim_feedforward=dim_feedforward, seq_len=seq_len,
        batch_size=batch_size, num_epochs=num_epochs,
    )
    analysis, state, wall = run_sweep(
        space, train, val, num_trials=num_trials, storage=storage,
        name="sweep",
    )
    assert len(state["trials"]) == num_trials, len(state["trials"])
    assert analysis.num_terminated() == num_trials
    best = analysis.best_result["validation_loss"]
    assert np.isfinite(best), best
    say(f"sweep: best_config={json.dumps(analysis.best_config)}")
    compile_block = state["compile"]
    # One architecture in the space, so one population program.
    assert compile_block["program_misses"] == 1, compile_block
    epochs = [t.training_iteration for t in analysis.trials]
    assert min(epochs) < max(epochs) == num_epochs, (
        f"ASHA cut nothing: {sorted(epochs)}"
    )
    out = {
        "trials": num_trials, "wall_s": round(wall, 1),
        "best_validation_loss": best,
        "compile_s": state.get("compile_time_total_s"),
        "compile": compile_block,
        "device_utilization": state.get("device_utilization"),
    }
    say(f"sweep: {json.dumps(out)}")
    return out


def phase_cohort(device, *, num_trials, num_epochs, seq_len, features,
                 n_samples, storage):
    """``tune.run`` with the default thread executor: ``num_trials``
    concurrent trial threads all leased the ONE ``device`` (the device list
    names it once per slot)."""
    import numpy as np

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=n_samples, seq_len=seq_len, num_features=features, seed=3
    )
    peak = {"running": 0}

    class Concurrency(tune.Callback):
        def __init__(self):
            self.live = set()

        def on_trial_start(self, trial):
            self.live.add(trial.trial_id)
            peak["running"] = max(peak["running"], len(self.live))

        def on_trial_complete(self, trial):
            self.live.discard(trial.trial_id)

        def on_trial_error(self, trial, error):
            self.live.discard(trial.trial_id)

    t0 = time.time()
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {"model": "simple_transformer", "d_model": tune.choice([16, 32]),
         "learning_rate": tune.loguniform(1e-4, 1e-1),
         "num_epochs": num_epochs, "batch_size": 32,
         "max_seq_length": max(seq_len, 64),
         "seed": tune.randint(0, 1000)},
        metric="validation_loss", mode="min", num_samples=num_trials,
        devices=[device] * num_trials, max_concurrent=num_trials,
        scheduler=tune.ASHAScheduler(
            max_t=num_epochs, grace_period=1, reduction_factor=2
        ),
        storage_path=storage, name="cohort", verbose=0,
        callbacks=[Concurrency()],
    )
    wall = time.time() - t0
    assert analysis.num_terminated() == num_trials, [
        (t.trial_id, t.status, t.error) for t in analysis.trials
    ]
    assert np.isfinite(analysis.best_result["validation_loss"])
    out = {
        "trials": num_trials, "wall_s": round(wall, 1),
        "peak_concurrent": peak["running"],
        "epochs_per_trial": sorted(
            t.training_iteration for t in analysis.trials
        ),
        "best_validation_loss": analysis.best_result["validation_loss"],
    }
    assert out["peak_concurrent"] > 1, out
    say(f"cohort: {json.dumps(out)}")
    return out, analysis, val


def phase_serve(analysis, val, *, batch_sizes, requests_per_size, storage):
    """Export the cohort's winner, serve it from one replica in this
    process, and hold the answers to ``model.apply``."""
    import numpy as np

    from distributed_machine_learning_tpu import compilecache, serve

    bundle_dir = os.path.join(storage, "bundle")
    serve.export_bundle(analysis, bundle_dir)
    bundle = serve.load_bundle(bundle_dir)
    model, variables = analysis.best_model()
    server = serve.PredictionServer(
        bundle, port=0, num_replicas=1, max_bucket=max(batch_sizes),
        max_batch_size=max(batch_sizes),
    )
    try:
        warm = server.warmup(np.asarray(val.x[:1], np.float32))
        host, port = server.start()
        base = f"http://{host}:{port}"
        worst = 0.0
        latencies = []
        for n in batch_sizes:
            x = np.asarray(val.x[:n], np.float32)
            want = np.asarray(
                model.apply(variables, x, deterministic=True), np.float32
            )
            for _ in range(requests_per_size):
                req = urllib.request.Request(
                    f"{base}/predict",
                    data=json.dumps({"instances": x.tolist()}).encode(),
                    headers={"Content-Type": "application/json"},
                )
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=60) as resp:
                    assert resp.status == 200, resp.status
                    body = json.loads(resp.read())
                latencies.append(time.perf_counter() - t0)
                got = np.asarray(body["predictions"], np.float32)
                assert got.shape == want.shape, (got.shape, want.shape)
                np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-3)
                worst = max(worst, float(np.abs(got - want).max()))
        with urllib.request.urlopen(f"{base}/metrics", timeout=60) as resp:
            metrics = json.loads(resp.read())
        with urllib.request.urlopen(f"{base}/healthz", timeout=60) as resp:
            health = json.loads(resp.read())
    finally:
        server.close()
    fresh = metrics["compile"]["new_programs_since_warmup"]
    assert fresh == 0, f"{fresh} programs compiled after warm-up"
    assert health["status"] == "ok", health
    total = len(batch_sizes) * requests_per_size
    assert metrics["requests_total"] >= total, metrics["requests_total"]
    counters = compilecache.get_counters().snapshot()
    out = {
        "requests": total, "batch_sizes": list(batch_sizes),
        "warm_programs": warm["programs"],
        "new_programs_since_warmup": fresh,
        "max_abs_err_vs_model_apply": worst,
        "request_p50_ms": round(sorted(latencies)[len(latencies) // 2] * 1e3, 3),
        "aot_exports": counters["aot_exports"],
        "aot_imports": counters["aot_imports"],
        "aot_unsupported": counters["aot_unsupported"],
    }
    say(f"serve: {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# Four chips (--chips 4): the sharded paths and what each is compared with


class ShardedEvidence:
    """A tune callback: at every trial result, record where the live
    split arrays lie (the trial's own state, while it is training)."""

    def __init__(self, min_devices):
        from distributed_machine_learning_tpu import tune

        evidence = self.evidence = []

        class _Cb(tune.Callback):
            def on_trial_result(self, trial, result):
                evidence.extend(sharded_live_arrays(min_devices))

        self.callback = _Cb()

    def device_ids(self):
        return sorted({i for _, _, ids in self.evidence for i in ids})


def phase_sharded_trial(devices, *, d_model, num_heads, dim_feedforward,
                        num_layers, seq_len, features, batch_size, n_samples,
                        num_epochs, storage):
    """One ``train_sharded_regressor`` trial on a dp 2 x tp 2 mesh against
    the same config and seed on a one-device mesh."""
    import numpy as np

    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import dummy_regression_data

    assert len(devices) == 4, devices
    train, val = dummy_regression_data(
        num_samples=n_samples, seq_len=seq_len, num_features=features, seed=5
    )
    config = {
        "model": "transformer", "d_model": d_model, "num_heads": num_heads,
        "dim_feedforward": dim_feedforward, "num_layers": num_layers,
        "max_seq_length": seq_len, "dropout": 0.0, "learning_rate": 1e-3,
        "lr_schedule": "constant", "num_epochs": num_epochs,
        "batch_size": batch_size, "seed": 1,
    }

    def run(name, mesh_shape, n, callbacks=None):
        return tune.run(
            tune.with_parameters(
                tune.train_sharded_regressor, train_data=train, val_data=val
            ),
            dict(config, mesh_shape=mesh_shape), metric="validation_loss",
            mode="min", num_samples=1, resources_per_trial={"devices": n},
            devices=list(devices), storage_path=storage, name=name,
            verbose=0, callbacks=callbacks,
        )

    ev = ShardedEvidence(min_devices=4)
    four = run("sharded_dp2tp2", {"dp": 2, "tp": 2}, 4, [ev.callback])
    one = run("sharded_one", {"dp": 1, "tp": 1}, 1)
    l4 = four.trials[0].metric_history("validation_loss")
    l1 = one.trials[0].metric_history("validation_loss")
    assert len(l4) == len(l1) == num_epochs, (l4, l1)
    assert four.trials[0].last_result["num_devices"] == 4
    np.testing.assert_allclose(l4, l1, rtol=MESH_LOSS_RTOL)
    assert ev.evidence, "no array split over 4 devices was live in the trial"
    assert ev.device_ids() == sorted(d.id for d in devices), ev.device_ids()
    tp_split = [e for e in ev.evidence if "tp" in e[1]]
    assert tp_split, f"no parameter split over tp: {ev.evidence[:4]}"
    out = {
        "loss_dp2tp2": l4, "loss_one_device": l1,
        "split_arrays_seen": len(ev.evidence),
        "shard_device_ids": ev.device_ids(),
        "example": tp_split[0],
    }
    say(f"sharded_trial: {json.dumps(out)}")
    return out


def phase_sharded_sweep(devices, *, d_model, num_heads, num_layers,
                        dim_feedforward, seq_len, features, batch_size,
                        num_trials, num_epochs, data_steps, storage):
    """``run_vectorized`` with the population axis over ``devices`` against
    the same sweep (same seeds) on one device."""
    import numpy as np

    from distributed_machine_learning_tpu.data import glucose_like_data

    train, val = glucose_like_data(
        num_steps=data_steps, num_features=features, interval=seq_len,
        stride=seq_len,
    )
    space = sweep_space(
        d_model=d_model, num_heads=num_heads, num_layers=num_layers,
        dim_feedforward=dim_feedforward, seq_len=seq_len,
        batch_size=batch_size, num_epochs=num_epochs,
    )
    ev = ShardedEvidence(min_devices=len(devices))
    many, many_state, _ = run_sweep(
        space, train, val, num_trials=num_trials, storage=storage,
        name="sweep_sharded", devices=list(devices),
        callbacks=[ev.callback],
    )
    one, _, _ = run_sweep(
        space, train, val, num_trials=num_trials, storage=storage,
        name="sweep_one", devices=[devices[0]],
    )
    assert many_state["population_sharded_over"] == len(devices), (
        many_state["population_sharded_over"]
    )
    assert many.best_trial.trial_id == one.best_trial.trial_id, (
        many.best_trial.trial_id, one.best_trial.trial_id
    )
    by_id = {t.trial_id: t for t in one.trials}
    for t in many.trials:
        np.testing.assert_allclose(
            t.metric_history("validation_loss"),
            by_id[t.trial_id].metric_history("validation_loss"),
            rtol=MESH_LOSS_RTOL,
        )
    assert ev.evidence, "no population array split over the devices was live"
    assert ev.device_ids() == sorted(d.id for d in devices), ev.device_ids()
    out = {
        "best_trial": many.best_trial.trial_id,
        "best_validation_loss": many.best_result["validation_loss"],
        "population_sharded_over": many_state["population_sharded_over"],
        "split_arrays_seen": len(ev.evidence),
        "shard_device_ids": ev.device_ids(),
        "example": ev.evidence[0],
    }
    say(f"sharded_sweep: {json.dumps(out)}")
    return out


def phase_ring(devices, *, batch, seq_len, heads, head_dim, use_flash="auto",
               interpret=False, tol=ATTENTION_TOL):
    """Ring attention over sp = len(devices), forward and backward, against
    dense attention on one device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_machine_learning_tpu.ops.attention import (
        dot_product_attention,
    )
    from distributed_machine_learning_tpu.parallel.ring_attention import (
        _use_flash_inner,
        ring_attention,
    )

    n = len(devices)
    mesh = Mesh(np.array(devices), ("sp",))
    q, k, v, w = random_qkvw((batch, seq_len, heads, head_dim), seed=1)
    flash_inner = bool(
        _use_flash_inner(use_flash, seq_len // n, seq_len // n, head_dim)
    )

    def ring(q, k, v):
        return ring_attention(
            q, k, v, mesh, use_flash=use_flash, flash_interpret=interpret
        )

    seq_sharded = NamedSharding(mesh, P(None, "sp", None, None))
    got = out_and_grads(
        ring, w, *(jax.device_put(a, seq_sharded) for a in (q, k, v))
    )
    assert len({s.device.id for s in got[0].addressable_shards}) == n
    want = out_and_grads(
        dot_product_attention, w,
        *(jax.device_put(a, devices[0]) for a in (q, k, v)),
    )
    errs = attention_errors(got, want, tol, "ring vs dense")
    out = {"sp": n, "seq_len": seq_len, "flash_inner": flash_inner,
           "normalized_max_err": errs}
    say(f"ring: {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------


def describe_environment(cache_dir: str) -> None:
    import importlib.metadata

    import jax

    from distributed_machine_learning_tpu.data.native import native_available

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"jax {jax.__version__}, libtpu {libtpu}, "
        f"devices: {len(jax.devices())} x {jax.devices()[0].device_kind}")
    say(f"compile cache dir: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    say(f"native_available: {native_available()}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): train, sweep, cohort, serve on one chip; "
             "4: only the sharded trial, the population-sharded sweep, ring "
             "attention and their one-device comparisons",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (jax sees {len(devices)} x "
            f"{devices[0].platform}); there is no CPU mode",
            file=sys.stderr,
        )
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax sees {len(devices)}", file=sys.stderr)
        return 3

    from distributed_machine_learning_tpu import compilecache

    cache_dir = compilecache.enable_persistent_cache()
    entries_at_start = compilecache.cache_entry_count()
    describe_environment(cache_dir)
    say(f"compile cache entries at start: {entries_at_start}")
    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.time()

    if args.chips == 4:
        four = list(devices[:4])
        with phase("sharded_trial", 600):
            phase_sharded_trial(
                four, d_model=512, num_heads=8, dim_feedforward=2048,
                num_layers=4, seq_len=512, features=16, batch_size=8,
                n_samples=80, num_epochs=3, storage=storage,
            )
        with phase("sharded_sweep", 600):
            phase_sharded_sweep(
                four, d_model=64, num_heads=4, num_layers=2,
                dim_feedforward=128, seq_len=96, features=16, batch_size=32,
                num_trials=48, num_epochs=4, data_steps=30_000,
                storage=storage,
            )
        with phase("ring", 600):
            ring = phase_ring(
                four, batch=1, seq_len=8192, heads=8, head_dim=64,
            )
            assert ring["flash_inner"], (
                "use_flash='auto' did not select the flash inner kernels "
                "at S/4 = 2048, head_dim 64 on a TPU"
            )
    else:
        with phase("train", 900):
            train = phase_train(
                d_model=512, num_heads=8, dim_feedforward=2048,
                num_layers=16, seq_len=2048, features=16, batch_size=8,
                n_train=24, n_val=8, num_epochs=2, storage=storage,
            )
            for attention_type in ("flash", "scaled_dot_product"):
                calls = train[attention_type]["pallas_calls"]
                assert calls > 0, (
                    f"attention_type={attention_type}: the compiled epoch "
                    f"program holds no tpu_custom_call — the Pallas kernel "
                    f"did not run"
                )
        with phase("sweep", 400):
            phase_sweep(
                d_model=64, num_heads=4, num_layers=2, dim_feedforward=128,
                seq_len=96, features=16, batch_size=32, num_trials=50,
                num_epochs=4, data_steps=30_000, storage=storage,
            )
        with phase("cohort", 400):
            _, cohort, cohort_val = phase_cohort(
                devices[0], num_trials=8, num_epochs=5, seq_len=20, features=8,
                n_samples=400, storage=storage,
            )
        with phase("serve", 300):
            phase_serve(
                cohort, cohort_val, batch_sizes=(1, 8, 32),
                requests_per_size=12, storage=storage,
            )

    tracker = compilecache.get_tracker()
    say(f"compile totals: backend_compiles="
        f"{tracker.total_backend_compiles()}, persistent_cache_hits="
        f"{tracker.total_cache_hits()}, compile_s="
        f"{tracker.total_seconds():.1f}, cache entries now "
        f"{compilecache.cache_entry_count()} (were {entries_at_start})")
    say(f"all phases passed in {time.time() - t_start:.1f}s")
    first = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The routed experts' counts of the training steps themselves: the epoch
program yields what the expert layers sow in each step, the trainable
puts them on its ``epoch`` span, and the benchmark's two readers turn the
spans (and the device trace) into per-layer numbers.  Tiny widths, CPU."""

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import program_spans as P
from benchmark import trace as T
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.models.moe import STATS_COLLECTION, collect_aux
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.tune._regression_program import (
    detect_call_convention,
    make_epoch_fn,
    make_forward,
)
from test_cca_moe_lm import HYBRID_TRIAL, TRIAL as CCA_TRIAL
from test_swa_moe_lm import TRIAL as SWA_TRIAL

FAMILIES = {
    "gated_hybrid_lm": HYBRID_TRIAL,
    "cca_moe_lm": CCA_TRIAL,
    "swa_moe_lm": SWA_TRIAL,
}
N_TRAIN, STEPS, BATCH, SEQ = 6, 3, 2, 16


def _family(name, seed=0):
    model = build_model(dict(FAMILIES[name]))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 97, (N_TRAIN, SEQ)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 97, (N_TRAIN, SEQ)), jnp.int32)
    variables, flag = detect_call_convention(model, x[:1])
    return model, flag, variables["params"], x, y


def _batches(epoch_key):
    """The epoch program's batches and dropout keys, drawn as it draws."""
    perm_key, key = jax.random.split(epoch_key)
    perm = jax.random.permutation(perm_key, N_TRAIN)
    perm = perm[: STEPS * BATCH].reshape(STEPS, BATCH)
    out = []
    for idx in perm:
        key, dkey = jax.random.split(key)
        out.append((idx, dkey))
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_epochs_counts_are_what_its_steps_sow(family):
    model, flag, params, x, y = _family(family)
    forward = make_forward(model, flag, has_bn=False)
    tx = optax.adam(0.0)  # the weights stay: every step routes under them
    epoch = jax.jit(make_epoch_fn(forward, tx, get_loss("cross_entropy"),
                                  N_TRAIN, STEPS, BATCH))
    key = jax.random.key(4)
    out = epoch(params, tx.init(params), {}, x, y, key)
    assert len(out) == 5
    counts = jax.device_get(out[4])

    sow = jax.jit(lambda xb, dkey: model.apply(
        {"params": params}, xb, mutable=[STATS_COLLECTION],
        rngs={"dropout": dkey},
        **{flag: False if flag == "deterministic" else True},
    )[1])
    pairs, loads, shares = [], [], []
    for idx, dkey in _batches(key):
        sown = sow(x[idx], dkey)
        leaves = jax.tree_util.tree_leaves_with_path(sown[STATS_COLLECTION])

        def layers(name):
            return [np.asarray(v) for path, v in leaves
                    if name in jax.tree_util.keystr(path)]

        pairs.append(sum(float(v.sum()) for v in layers("local_pairs")))
        loads.append(max(float(v.max()) for v in layers("load_max_over_mean")))
        shares.append(float(np.mean(np.concatenate(
            [v.ravel() for v in layers("stride_row_share")]))))
    assert counts["moe_pairs"].dtype == np.int32
    assert int(counts["moe_pairs"]) == int(sum(pairs)) > 0
    assert float(counts["moe_load_max_over_mean"]) == pytest.approx(
        np.mean(loads), rel=1e-6)
    assert float(counts["moe_stride_row_share"]) == pytest.approx(
        np.mean(shares), rel=1e-6)
    assert 1.0 <= float(counts["moe_load_max_over_mean"])
    assert 0.0 <= float(counts["moe_stride_row_share"]) <= 1.0


def _capture_grads():
    """An optimizer that leaves the weights and keeps the step's gradients
    as its state."""
    return optax.GradientTransformation(
        init=lambda p: jax.tree.map(jnp.zeros_like, p),
        update=lambda g, s, p=None: (jax.tree.map(jnp.zeros_like, g), g),
    )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_steps_loss_and_gradients_never_see_the_counts(family):
    model, flag, params, x, y = _family(family, seed=1)
    loss_fn = get_loss("cross_entropy")
    kwargs = {flag: False if flag == "deterministic" else True}
    tx = _capture_grads()
    epoch = jax.jit(make_epoch_fn(make_forward(model, flag, has_bn=False),
                                  tx, loss_fn, N_TRAIN, 1, BATCH))
    key = jax.random.key(9)
    _, grads, _, loss, counts = epoch(params, tx.init(params), {}, x, y, key)
    assert int(counts["moe_pairs"]) > 0

    perm_key, drop0 = jax.random.split(key)
    idx = jax.random.permutation(perm_key, N_TRAIN)[:BATCH]
    _, dkey = jax.random.split(drop0)

    def by_hand(p):  # the objective, the stats collection left out
        logits, mut = model.apply({"params": p}, x[idx], mutable=["moe"],
                                  rngs={"dropout": dkey}, **kwargs)
        return loss_fn(logits, y[idx]) + collect_aux(mut)

    want_loss, want_grads = jax.jit(jax.value_and_grad(by_hand))(params)
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_a_dense_models_epoch_returns_its_four_values():
    model = build_model({"model": "transformer", "d_model": 16, "num_heads": 2,
                         "num_layers": 1, "dim_feedforward": 32})
    x = jnp.ones((8, 12, 3), jnp.float32)
    y = jnp.ones((8, 1), jnp.float32)
    variables, flag = detect_call_convention(model, x[:1])
    forward = make_forward(model, flag, has_bn="batch_stats" in variables)
    assert len(forward(variables["params"], {}, x[:2], jax.random.key(0),
                       train=True)) == 3
    tx = optax.adam(1e-3)
    out = jax.jit(make_epoch_fn(forward, tx, get_loss("mse"), 8, 2, 4))(
        variables["params"], tx.init(variables["params"]),
        variables.get("batch_stats", {}), x, y, jax.random.key(1))
    assert len(out) == 4


def test_the_trainable_puts_the_counts_on_its_epoch_span(tmp_path):
    """Under a profiler session the ``epoch`` spans carry the four attrs,
    as the benchmark's loader reads them, and the registry adds them up."""
    from benchmark import datagen_tokens
    from distributed_machine_learning_tpu import obs, tune
    from distributed_machine_learning_tpu.data.loader import Dataset

    xt, yt, xv, yv = datagen_tokens.make_sequences(
        3, n_train=4, n_val=2, seq_len=SEQ, vocab=97)
    registry = obs.get_registry()
    before = {n: registry.get(n) for n in (
        "moe.step_pairs", "moe.steps", "moe.step_load_max_over_mean_sum",
        "moe.step_stride_row_share_sum")}
    tune.clear_program_cache()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            analysis = tune.run(
                tune.with_parameters(tune.train_regressor,
                                     train_data=Dataset(xt, yt),
                                     val_data=Dataset(xv, yv)),
                dict(CCA_TRIAL, optimizer="adam", loss_function="cross_entropy",
                     learning_rate=1e-2, weight_decay=0.0, batch_size=2,
                     max_seq_length=SEQ, num_epochs=2, seed=5),
                metric="validation_loss", mode="min", num_samples=1,
                storage_path=str(tmp_path / "runs"), name="counts", verbose=0,
            )
    finally:
        jax.profiler.stop_trace()
    assert analysis.trials[0].results[-1]["epoch"] == 1
    path = T.find_xplane(str(tmp_path / "trace"))
    window = T.annotation_window(T.load(path), "bench:window")
    epochs = P.named(P.load(path, window), "epoch")
    assert len(epochs) == 2
    for span in epochs:
        assert span.attrs["moe_steps"] == 2
        assert span.attrs["moe_pairs"] > 0
        assert 1.0 <= span.attrs["moe_load_max_over_mean"] <= 8.0
        assert 0.0 <= span.attrs["moe_stride_row_share"] <= 1.0
    after = {n: registry.get(n) - v for n, v in before.items()}
    assert after["moe.steps"] == 4
    assert after["moe.step_pairs"] == sum(s.attrs["moe_pairs"] for s in epochs)
    assert after["moe.step_load_max_over_mean_sum"] == pytest.approx(
        sum(2 * s.attrs["moe_load_max_over_mean"] for s in epochs), rel=1e-5)


# -- the benchmark's readers on a synthetic trace ------------------------------


def S(name, start, dur, **attrs):
    return P.Span(name, float(start), float(dur), attrs)


def _run(lines, trace=None, sources=None, window=(0.0, 20.0)):
    run = types.SimpleNamespace(
        trace=trace or T.Trace(devices={}), trace_window=window,
        work_dir="/nonexistent", facts={"program_spans": lines})
    if sources is not None:
        run.facts["op_sources"] = sources
    return run


def read(reader, spec, run):
    return importlib.import_module(f"benchmark.readers.{reader}").read(spec, run)


PAIRS = {"span": "epoch", "value": "moe_pairs", "per": "moe_steps"}
LOAD = {"span": "epoch", "value": "moe_load_max_over_mean",
        "weight": "moe_steps", "per": "moe_steps"}
NS = {"scope": "routed_experts", "program": "jit_epoch", "span": "epoch",
      "attr": "moe_pairs", "units_per_s": 1e9}


def _two_epochs():
    """An epoch of 2 steps and one of 4, with their counts; the evaluation
    and report between them carry none."""
    return [[
        S("epoch", 1.0, 4.0, epoch=0, moe_pairs=100, moe_steps=2,
          moe_load_max_over_mean=2.0),
        S("epoch.readback", 4.0, 1.0),
        S("report", 5.0, 1.0, iteration=1),
        S("epoch", 7.0, 8.0, epoch=1, moe_pairs=500, moe_steps=4,
          moe_load_max_over_mean=5.0),
    ]]


def test_span_attrs_are_weighted_by_the_steps_of_each_epoch():
    run = _run(_two_epochs())
    assert read("program_span_attr", PAIRS, run) == pytest.approx(600 / 6)
    # 2.0 for 2 steps and 5.0 for 4: (4 + 20) / 6, not the epochs' mean 3.5
    assert read("program_span_attr", LOAD, run) == pytest.approx(4.0)


def test_time_per_pair_counts_the_scope_in_the_epoch_program_alone():
    dev = T.DeviceTrace(
        modules=[("jit_epoch(7)", 1.5, 3.0), ("jit_evaluate(8)", 4.5, 0.5),
                 ("jit_epoch(7)", 7.5, 6.0)],
        ops=[
            ("%fusion.1 = f32[] fusion()", 2.0, 1.0),     # experts
            ("%fusion.2 = f32[] fusion()", 3.0, 0.5),     # attention
            ("%fusion.1 = f32[] fusion()", 4.6, 0.2),     # evaluation's experts
            ("%while.3 = () while()", 8.0, 4.0),          # loop holding both
            ("%fusion.1 = f32[] fusion()", 8.5, 1.5),
            ("%fusion.2 = f32[] fusion()", 10.0, 1.0),
        ])
    sources = {
        "jit_epoch": {"fusion.1": "jit(epoch)/jvp(block)/routed_experts/dot",
                      "fusion.2": "jit(epoch)/jvp(block)/causal_attention/dot",
                      "while.3": "jit(epoch)/while"},
        "jit_evaluate": {"fusion.1": "jit(evaluate)/block/routed_experts/dot"},
    }
    run = _run(_two_epochs(), T.Trace(devices={"/device:TPU:0": dev}), sources)
    # 1.0 + 1.5 seconds of the experts' operations over 600 pairs
    assert read("scope_per_attr", NS, run) == pytest.approx(2.5 / 600 * 1e9)


def test_a_trace_without_the_counts_reads_nothing():
    dev = T.DeviceTrace(modules=[("jit_epoch(7)", 1.5, 3.0)],
                        ops=[("%fusion.1 = f32[] fusion()", 2.0, 1.0)])
    trace = T.Trace(devices={"/device:TPU:0": dev})
    sources = {"jit_epoch": {"fusion.1": "jit(epoch)/routed_experts/dot"}}
    bare = [[S("epoch", 1.0, 4.0, epoch=0), S("report", 5.0, 1.0)]]
    for reader, spec in (("program_span_attr", PAIRS),
                         ("program_span_attr", LOAD),
                         ("scope_per_attr", NS)):
        assert read(reader, spec, _run(bare, trace, sources)) is None
        assert read(reader, spec, _run([], trace, sources)) is None
    untraced = types.SimpleNamespace(trace=None, facts={})
    assert read("program_span_attr", PAIRS, untraced) is None
    assert read("scope_per_attr", NS, untraced) is None

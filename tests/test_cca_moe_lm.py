"""The compressed-convolutional-attention language model (family
``cca_moe_lm``) against its plain reference, at tiny widths on the CPU,
with seeded random weights.

The program runs in float32 here, so it and the reference differ by the
order of their sums only; each tolerance says what it allows for.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_tokens
from benchmark.drivers import train_cca_lm
from benchmark.reference import cca_moe_lm as ref
from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.models import build_model, cca_lm, hybrid_lm
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.tune.trial import TrialStatus

# The published key names at widths a CPU test can afford: 2 layers, 4
# query heads on 2 key-value heads, 16 experts of which ids 8-15 are held.
REF_CFG = {
    "num_hidden_layers": 2, "hidden_size": 64, "vocab_size": 97,
    "rms_norm_eps": 1e-5, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "cca_time0": 2, "cca_time1": 2,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000}},
    "router_hidden_size": 16, "num_experts": 8, "num_experts_per_tok": 1,
    "moe_intermediate_size": 32,
    "published": {"num_experts": 16}, "held_experts": [8, 8],
}
TRIAL = {
    "model": "cca_moe_lm", "vocab_size": 97, "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 32, "rotary_dim": 16,
    "rope_theta": 5e6, "conv_time0": 2, "conv_time1": 2, "num_experts": 16,
    "top_k": 1, "expert_width": 32, "router_hidden": 16,
    "held_experts": [8, 8], "expert_tile": 8, "compute_dtype": "float32",
}
# Float32 sums in another order: a few units in the seventh digit a sum,
# over two layers and a few hundred terms.
ATOL = 2e-5


def _spread(params):
    """Weights at which a wrong formula shows: norm scales and the
    temperature off 1, router biases off 0, and a router that spreads its
    choices over the experts (0.02-normal weights hardly do)."""
    def move(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "temperature" in name:
            return a + 0.1 * jnp.arange(a.size, dtype=a.dtype).reshape(a.shape) / a.size
        if "bias" in name and "router" in name:
            return a + 0.05
        if "router" in name and "kernel" in name:
            return a * 20.0
        return a

    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def stack():
    """The 2-layer stack on both sides, each compiled once: (logits, loss,
    gradients in the reference's names) of the program and of the
    reference, from the same weights."""
    model = build_model(TRIAL)
    tokens = jax.random.randint(jax.random.key(0), (2, 21), 0, 97)
    targets = jax.random.randint(jax.random.key(1), (2, 21), 0, 97)
    params = _spread(
        jax.jit(model.init)({"params": jax.random.key(2)}, tokens)["params"]
    )

    def program_loss(p):
        logits = model.apply({"params": p}, tokens)
        return get_loss("cross_entropy")(logits, targets), logits

    def reference_loss(p):
        logits = ref.forward(p, tokens, REF_CFG)
        return jnp.mean(ref.token_losses(p, tokens, targets, REF_CFG)), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(program_loss, has_aux=True)
    )(params)
    as_ref = train_cca_lm.to_reference(params)
    (want_loss, want_logits), want_grads = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True)
    )(as_ref)
    return ((logits, loss, train_cca_lm.to_reference(grads)),
            (want_logits, want_loss, want_grads), (as_ref, tokens, targets))


def test_forward_and_loss_match_the_reference(stack):
    (logits, loss, _), (want, want_loss, _), _ = stack
    assert logits.shape == (2, 21, 97)
    np.testing.assert_allclose(logits, want, atol=ATOL)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)


_LEAVES = sorted(ref.parameter_shapes(REF_CFG))


def test_the_two_sides_name_the_same_leaves(stack):
    (_, _, grads), (_, _, want), _ = stack
    assert set(grads) == set(want) == set(_LEAVES)
    assert {k: v.shape for k, v in want.items()} == ref.parameter_shapes(REF_CFG)


@pytest.mark.parametrize("name", _LEAVES)
def test_gradient_leaf_matches_the_reference(stack, name):
    (_, _, grads), (_, _, want), _ = stack
    # Against the leaf's own size: float32 round-off of a backward pass
    # through two layers is a few 1e-6 of the largest entry.
    scale = float(jnp.max(jnp.abs(want[name])))
    assert scale > 0, f"{name} has no gradient"
    np.testing.assert_allclose(
        np.asarray(grads[name]) / scale, np.asarray(want[name]) / scale,
        atol=2e-4,
    )


def test_the_tied_tables_gradient_is_the_lookups_plus_the_heads(stack):
    """The embedding is read twice, as the table of the lookup and as the
    head's matrix: its gradient in the program is the sum of the two that
    the reference gives when each reading has a table of its own."""
    (_, _, grads), _, (params, tokens, targets) = stack

    def loss(lookup, head):
        x = ref.hidden_states(dict(params, embed=lookup), tokens, REF_CFG)
        logits = jnp.einsum("bsd,vd->bsv", x, head, precision=ref.HIGHEST)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))

    g_lookup, g_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(
        params["embed"], params["embed"]
    )
    scale = float(jnp.max(jnp.abs(g_lookup + g_head)))
    # Neither alone: each is a visible part of the whole.
    assert float(jnp.max(jnp.abs(g_lookup))) > 1e-2 * scale
    assert float(jnp.max(jnp.abs(g_head))) > 1e-2 * scale
    np.testing.assert_allclose(
        np.asarray(grads["embed"]) / scale,
        np.asarray(g_lookup + g_head) / scale, atol=2e-4,
    )


@pytest.mark.parametrize("block_rows,rows_used,ahead", [
    (2, None, False), (1, None, False), (2, 1, False), (1, None, True),
])
def test_the_references_layerwise_step_is_the_whole_models(
        stack, block_rows, rows_used, ahead):
    """``make_step`` takes the chain rule a layer at a time (a compiled
    forward and a compiled ``jax.vjp`` of one layer, called for each): its
    loss and its Adam step are those of ``jax.value_and_grad`` over the
    whole model, with the batch whole or in blocks of rows, with a row
    left out, compiled at the first call or ahead of it."""
    from benchmark.reference.regressor import adam_init, adam_update

    _, _, (params, tokens, targets) = stack
    used = rows_used or 2

    def whole_loss(p):
        nll = ref.token_losses(p, tokens, targets, REF_CFG)
        return jnp.sum(nll[:used]) / (used * tokens.shape[1])

    want_loss, grads = jax.jit(jax.value_and_grad(whole_loss))(params)
    want, _ = adam_update(params, grads, adam_init(params), 1e-2, 0.0, 100)
    step = ref.make_step(REF_CFG, 2, block_rows, 100, rows_used=rows_used,
                         seq_len=tokens.shape[1] if ahead else None)
    # The step takes its state for good: hand it copies.
    fresh = jax.tree.map(jnp.copy, params)
    got, opt, loss = step(fresh, adam_init(fresh), tokens, targets, None,
                          1e-2, 0.0)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert int(opt["count"]) == 1 and set(got) == set(want)
    for name in want:
        # Adam's first step moves an entry by the rate, 1e-2, times
        # g / (|g| + 1e-8): the same to float32's rounding but where a
        # gradient of the order of the epsilon sums in another order.
        np.testing.assert_allclose(got[name], want[name], atol=2e-4,
                                   err_msg=name)
    evaluate = ref.make_eval(REF_CFG, block_rows)
    np.testing.assert_allclose(
        evaluate(params, tokens, targets),
        jnp.mean(ref.token_losses(params, tokens, targets, REF_CFG)), rtol=1e-6)


def _init_sides(seed=11, **sizes):
    trial = dict(TRIAL, seed=seed, **sizes)
    cfg = dict(REF_CFG, num_hidden_layers=trial["num_layers"])
    return (train_cca_lm.program_init(trial, np.zeros((1, 4), np.int32)),
            train_cca_lm.reference_init(trial, cfg), cfg)


def test_the_program_starts_from_the_references_weights():
    """The program's initialisation from a trial's seed, renamed by
    ``to_reference``, is the reference's own ``init_params`` to the bit in
    every leaf (the same generator bits through the same float32
    operations: no tolerance); and the reference's draws are what the
    configuration's ``assumed`` block says."""
    got, want, cfg = _init_sides()
    assert train_cca_lm.init_gap(got, want) == 0.0
    assert {k: v.shape for k, v in want.items()} == ref.parameter_shapes(cfg)
    for name, w in want.items():
        leaf = name.split(".")[-1]
        if leaf in ("in_norm", "post_norm", "final_norm", "norm", "temperature"):
            assert (w == 1.0).all(), name
        elif leaf.endswith("_b") and "router" in name:
            assert not w.any(), name
        elif leaf.startswith("conv0"):
            assert np.abs(w).max() <= 2 ** -0.5 and w.std() > 0.3, name
        elif leaf.startswith("conv1"):
            assert np.abs(w).max() <= 64 ** -0.5 and w.std() > 0.05, name
        elif w.size >= 2048:
            # normal(0.02): the spread of a few thousand draws to a tenth.
            assert abs(float(w.std()) / 0.02 - 1.0) < 0.1, name
            assert abs(float(w.mean())) < 0.002, name
    # A leaf left out, or drawn from another key, is no small gap.
    assert train_cca_lm.init_gap({k: got[k] for k in list(got)[1:]}, want) \
        == float("inf")
    assert train_cca_lm.init_gap(dict(got, embed=got["embed"][::-1]), want) > 0.5


@pytest.mark.parametrize("fault", ["std", "conv_bound"])
def test_a_program_that_draws_otherwise_does_not_start_there(fault, monkeypatch):
    monkeypatch.setattr(train_cca_lm.train_lm, "_COMPILED", {})
    if fault == "std":
        monkeypatch.setattr(hybrid_lm, "_normal_init",
                            jax.nn.initializers.normal(0.021))
        monkeypatch.setattr(cca_lm, "_normal_init",
                            jax.nn.initializers.normal(0.021))
    else:
        monkeypatch.setattr(
            cca_lm, "_fan_in_uniform",
            lambda fan_in: lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -1.0, 1.0),
        )
    got, want, _ = _init_sides(num_layers=1)
    assert train_cca_lm.init_gap(got, want) > 1e-3


@pytest.mark.parametrize("head_dim,heads,kv_heads", [(32, 4, 2), (128, 8, 2)])
def test_cca_mixer_matches_the_reference_and_is_causal(head_dim, heads, kv_heads):
    """Both convolutions, the query-key mean, the L2 normalisation with
    its temperature, partial rotary, the value shift and grouped causal
    attention; the second case has the published heads and head size."""
    mixer = cca_lm.CCAMixer(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        rotary_dim=head_dim // 2, dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.key(0), (2, 19, 64))
    params = jax.jit(mixer.init)(jax.random.key(1), x)["params"]
    params["temperature"] = jnp.asarray([0.7, 1.4])
    cfg = dict(REF_CFG, num_attention_heads=heads, head_dim=head_dim,
               num_key_value_heads=kv_heads)
    p = {
        "wq": params["q_proj"]["kernel"], "wk": params["k_proj"]["kernel"],
        "wv": params["v_proj"]["kernel"],
        "wv_shift": params["v_shift_proj"]["kernel"],
        "wo": params["o_proj"]["kernel"],
        "conv0_w": params["conv0_weight"], "conv0_b": params["conv0_bias"],
        "conv1_w": params["conv1_weight"], "conv1_b": params["conv1_bias"],
        "temperature": params["temperature"],
    }
    want = jax.jit(lambda p, x: ref.cca(p, x, cfg, query_block=8))(p, x)
    apply = jax.jit(mixer.apply)
    np.testing.assert_allclose(apply({"params": params}, x), want, atol=ATOL)
    # Causal: a change at position 12 moves no output before it, through
    # both convolutions and the value shift, and moves 12 and 13 (the
    # shift and each convolution reach one position on).
    out2 = apply({"params": params}, x.at[:, 12].add(1.0))
    np.testing.assert_array_equal(out2[:, :12], apply({"params": params}, x)[:, :12])
    for t in (12, 13, 18):
        assert float(jnp.max(jnp.abs(out2[:, t] - want[:, t]))) > 1e-4, t


def _moe_layer(held, renormalise=False):
    return hybrid_lm.DroplessMoE(
        num_experts=16, top_k=1, expert_width=32, shared_width=0,
        held_experts=held, tile=8, dtype=jnp.float32,
        router=cca_lm.RouterMLP(16, 16, parent=None), renormalise=renormalise,
    )


def _moe_reference_params(params):
    """The expert layer's leaves under the reference's ``moe.`` names."""
    router = params["router"]
    out = {name: params[name] for name in ("w_gate", "w_up", "w_down")}
    out.update({
        "router.down_w": router["down"]["kernel"],
        "router.down_b": router["down"]["bias"],
        "router.norm": router["norm"]["scale"],
        "router.out_w": router["out"]["kernel"],
    })
    for j in range(ref.ROUTER_DEPTH):
        out[f"router.h{j}_w"] = router[f"hidden_{j}"]["kernel"]
        out[f"router.h{j}_b"] = router[f"hidden_{j}"]["bias"]
    return out


def _moe_sides(held):
    """(the layer, its spread parameters, x, the reference's parameters
    under its ``moe.`` names)."""
    layer = _moe_layer(held)
    x = jax.random.normal(jax.random.key(0), (2, 29, 64))
    params = _spread(jax.jit(layer.init)(jax.random.key(1), x)["params"])
    return layer, params, x, _moe_reference_params(params)


def test_expert_layer_matches_the_reference():
    layer, params, x, as_ref = _moe_sides((8, 8))
    got, sown = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=[hybrid_lm.STATS_COLLECTION]))(params, x)
    want = jax.jit(lambda p, x: ref.moe(p, x, REF_CFG))(as_ref, x)
    np.testing.assert_allclose(got, want, atol=ATOL)
    _, top_e = ref.route(
        {k[len("router."):]: v for k, v in as_ref.items()
         if k.startswith("router.")}, x.reshape(58, 64), REF_CFG)
    local = int(jnp.sum(top_e >= 8))
    assert 0 < local < 58          # some tokens chose an absent expert
    assert len(set(np.asarray(top_e).ravel().tolist())) >= 4
    assert float(sown[hybrid_lm.STATS_COLLECTION]["local_pairs"][0]) == local


def test_the_two_shares_add_up_to_the_uncut_layer():
    """16 experts in 2 shares of 8 (the deployment's two chips): what the
    shares give adds up to the uncut reference's layer output; there is no
    shared expert to count once."""
    whole, params, x, as_ref = _moe_sides((0, 16))
    uncut = jax.jit(
        lambda p, x: ref.moe(p, x, dict(REF_CFG, held_experts=[0, 16]))
    )(as_ref, x)
    total = jnp.zeros_like(uncut)
    for first in (0, 8):
        share = dict(params)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = params[name][first:first + 8]
        total = total + jax.jit(_moe_layer((first, 8)).apply)(
            {"params": share}, x)
    assert float(jnp.max(jnp.abs(uncut))) > 1e-3
    np.testing.assert_allclose(total, uncut, atol=ATOL)


@pytest.mark.parametrize("renormalise", [False, True])
def test_top1_weighting_reaches_the_router(renormalise):
    """Weighted by its own probability the one chosen expert hands the
    router MLP a gradient; divided by the sum of the top-1 probabilities
    the weight is the constant 1 and every leaf of the router gets nought
    to rounding (what ``renormalise`` would do to this family)."""
    _, params, x, _ = _moe_sides((8, 8))
    layer = _moe_layer((8, 8), renormalise)
    grads = jax.jit(jax.grad(
        lambda p: jnp.sum(layer.apply({"params": p}, x) ** 2)
    ))(params)
    sizes = [float(jnp.max(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(grads["router"])]
    if renormalise:
        # p / p: the quotient's two terms cancel to float32's last bits.
        assert max(sizes) < 1e-8
    else:
        assert min(sizes) > 1e-5
    assert float(jnp.max(jnp.abs(grads["w_down"]))) > 0.0


# The hybrid family's expert layer and whole loss, lowered on the
# CPU (jax 0.9.0; `as_text()` of the jitted gradient, shapes
# only) from the tree whose routed experts walk each held expert's run in
# strides (`ops/grouped_experts.py _walk`): the router part, the weighting
# rule and the shared expert's absence are arguments whose defaults leave
# its program as it was.  The whole loss is pinned again since the delta
# rule takes q and k at the key heads and repeats them itself on XLA's
# path (``ops/gated_delta.py``): the same operations in another order,
# whose loss and every gradient equal the earlier program's to the bit.
PARENT_HLO_SHA256 = {
    "moe": "ae995ac50b10552d022ee88ef496f61725edc168f5493a1d07d495b19d35a606",
    "stack": "3c26d229f5011c350e53ae0c281d561879e8c60e8d7ea5c97dd6d6e63865bc06",
}


# ``tests/test_gated_hybrid_lm.py``'s tiny trial, which the pinned hash is of.
HYBRID_TRIAL = {
    "model": "gated_hybrid_lm", "vocab_size": 97, "num_layers": 4,
    "d_model": 64, "full_attention_interval": 4, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 32, "rotary_dim": 8,
    "rope_theta": 1e7, "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "conv_width": 4, "delta_chunk": 8, "num_experts": 16, "top_k": 3,
    "expert_width": 32, "shared_width": 32, "held_experts": [4, 4],
    "expert_tile": 8, "compute_dtype": "float32",
}


@pytest.mark.parametrize("what", sorted(PARENT_HLO_SHA256))
def test_the_hybrid_familys_hlo_is_the_parents(what):
    if what == "moe":
        layer = hybrid_lm.DroplessMoE(
            num_experts=16, top_k=3, expert_width=32, shared_width=32,
            held_experts=(4, 4), tile=8, dtype=jnp.float32,
        )
        x = jax.ShapeDtypeStruct((2, 13, 64), jnp.float32)
        params = jax.eval_shape(
            lambda x: layer.init(jax.random.key(1), x)["params"], x)
        text = jax.jit(jax.grad(
            lambda p, x: jnp.sum(layer.apply({"params": p}, x))
        )).lower(params, x).as_text()
    else:
        model = build_model(HYBRID_TRIAL)
        tokens = jax.ShapeDtypeStruct((2, 21), jnp.int32)
        params = jax.eval_shape(
            lambda t: model.init({"params": jax.random.key(0)}, t)["params"],
            tokens)

        def loss(p, x, y):  # the text carries the function's name
            return get_loss("cross_entropy")(model.apply({"params": p}, x), y)

        text = jax.jit(jax.value_and_grad(loss)).lower(
            params, tokens, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_HLO_SHA256[what]


# -- through tune.run --------------------------------------------------------


def _token_data(seed=3, seq_len=16):
    xt, yt, xv, yv = datagen_tokens.make_sequences(
        seed, n_train=4, n_val=2, seq_len=seq_len, vocab=97
    )
    return Dataset(xt, yt), Dataset(xv, yv)


def _tune_run(tmp_path, name, resume=False):
    train, val = _token_data()
    return tune.run(
        tune.with_parameters(tune.train_regressor, train_data=train,
                             val_data=val),
        dict(TRIAL, optimizer="adam", loss_function="cross_entropy",
             learning_rate=1e-2, weight_decay=0.0, batch_size=2,
             max_seq_length=16, num_epochs=2, checkpoint_freq=1, seed=5),
        metric="validation_loss", mode="min", num_samples=1,
        storage_path=str(tmp_path), name=name, verbose=0, resume=resume,
    )


def test_two_epochs_through_tune_run_with_a_checkpoint_and_a_restore(tmp_path):
    """``tune.run(train_regressor)`` trains the family for two epochs and
    saves after each; a run cut after its first epoch and resumed restores
    that checkpoint and reports the second epoch's losses to the bit."""
    tune.clear_program_cache()
    straight = _tune_run(tmp_path, "straight").trials[0]
    assert straight.status == TrialStatus.TERMINATED
    first, second = straight.results
    assert second["train_loss"] < first["train_loss"] < np.log(97) * 1.05
    assert first["moe_local_pairs"] > 0    # the expert layers sow their counts
    assert 0.0 <= first["moe_stride_row_share"] <= 1.0

    cut = _tune_run(tmp_path, "cut")
    root, trial_id = cut.root, cut.trials[0].trial_id
    state_path = os.path.join(root, "experiment_state.json")
    with open(state_path) as f:
        state = json.load(f)
    for t in state["trials"]:
        t["status"] = "RUNNING"
    with open(state_path, "w") as f:
        json.dump(state, f)
    results = os.path.join(root, trial_id, "result.jsonl")
    with open(results) as f:
        lines = [line for line in f if line.strip()]
    with open(results, "w") as f:
        f.writelines(lines[:1])
    ckdir = os.path.join(root, trial_id, "checkpoints")
    newest = sorted(n for n in os.listdir(ckdir) if "000002" in n)
    assert newest, os.listdir(ckdir)
    for name in newest:
        os.unlink(os.path.join(ckdir, name))

    resumed = _tune_run(tmp_path, "cut", resume=True).trials[0]
    assert resumed.status == TrialStatus.TERMINATED
    assert [r["training_iteration"] for r in resumed.results] == [1, 2]
    for key in ("train_loss", "validation_loss"):
        assert resumed.results[1][key] == second[key], key
    tune.clear_program_cache()

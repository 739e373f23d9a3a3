"""The hybrid language model (family ``gated_hybrid_lm``) against its plain
reference, at tiny widths on the CPU, with seeded random weights.

The program runs in float32 here, so it and the reference differ by the
order of their sums only; each tolerance says what it allows for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_tokens
from benchmark.drivers import train_lm
from benchmark.reference import gated_hybrid_lm as ref
from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data.loader import Dataset
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.models import hybrid_lm
from distributed_machine_learning_tpu.ops import grouped_experts
from distributed_machine_learning_tpu.ops.gated_delta import (
    chunk_gated_delta_rule,
)
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.tune._regression_program import (
    stage_data,
)
from distributed_machine_learning_tpu.tune.checkpoint import (
    AsyncCheckpointWriter,
)

# The published key names at widths a CPU test can afford: 4 layers of the
# published 3 : 1 pattern, 16 experts of which ids 4-7 are held, top-3.
REF_CFG = {
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "hidden_size": 64, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "published": {"num_experts": 16}, "held_experts": [4, 4],
}
TRIAL = {
    "model": "gated_hybrid_lm", "vocab_size": 97, "num_layers": 4,
    "d_model": 64, "full_attention_interval": 4, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 32, "rotary_dim": 8,
    "rope_theta": 1e7, "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "conv_width": 4, "delta_chunk": 8, "num_experts": 16, "top_k": 3,
    "expert_width": 32, "shared_width": 32, "held_experts": [4, 4],
    "expert_tile": 8, "compute_dtype": "float32",
}
# Float32 sums in another order: a few units in the seventh digit a sum,
# over four layers and a few hundred terms.
ATOL = 2e-5


@pytest.fixture(scope="module")
def stack():
    """The 4-layer stack on both sides, each compiled once: (logits, loss,
    gradients in the reference's names) of the program and of the
    reference, from the same weights; S is no multiple of the chunk."""
    model = build_model(TRIAL)
    tokens = jax.random.randint(jax.random.key(0), (2, 21), 0, 97)
    targets = jax.random.randint(jax.random.key(1), (2, 21), 0, 97)
    params = jax.jit(model.init)({"params": jax.random.key(2)}, tokens)["params"]
    # Norm scales start at nought: move them, so that a wrong (1 + w) shows.
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 if "scale" in jax.tree_util.keystr(path) else a,
        params,
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
    (want_loss, want_logits), want_grads = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True)
    )(train_lm.to_reference(params, REF_CFG))
    return ((logits, loss, train_lm.to_reference(grads, REF_CFG)),
            (want_logits, want_loss, want_grads))


def test_forward_and_loss_match_the_reference(stack):
    (logits, loss, _), (want, want_loss, _) = stack
    assert logits.shape == (2, 21, 97)
    np.testing.assert_allclose(logits, want, atol=ATOL)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)


def test_every_gradient_leaf_matches_the_reference(stack):
    (_, _, grads), (_, _, want) = stack
    assert set(grads) == set(want) == set(ref.parameter_shapes(REF_CFG))
    for name, w in want.items():
        # Against the leaf's own size: float32 round-off of a backward
        # pass through four layers is a few 1e-6 of the largest entry.
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, f"{name} has no gradient"
        np.testing.assert_allclose(
            np.asarray(grads[name]) / scale, np.asarray(w) / scale,
            atol=2e-4, err_msg=name,
        )


def _init_sides(layers, every, seed=11):
    """(program's, reference's) starting weights of a stack of ``layers``
    layers, every ``every``-th of full attention, in the reference's names."""
    trial = dict(TRIAL, num_layers=layers, full_attention_interval=every,
                 seed=seed)
    cfg = dict(REF_CFG, num_hidden_layers=layers, full_attention_interval=every)
    return (train_lm.program_init(trial, cfg, np.zeros((1, 4), np.int32)),
            train_lm.reference_init(trial, cfg), cfg)


def test_the_program_starts_from_the_references_weights():
    """The program's initialisation from a trial's seed, renamed by
    ``to_reference``, is the reference's own ``init_params`` to the bit in
    every leaf (the same generator bits through the same float32
    operations: no tolerance), for a layer of each kind; and the
    reference's draws are what the configuration's ``assumed`` block says."""
    got, want, cfg = _init_sides(2, 2)
    assert train_lm.init_gap(got, want) == 0.0
    assert {k: v.shape for k, v in want.items()} == ref.parameter_shapes(cfg)
    for name, w in want.items():
        leaf = name.split(".")[-1]
        if leaf in ("in_norm", "post_norm", "final_norm", "q_norm", "k_norm"):
            assert not w.any(), name           # zero-centred: from nought
        elif leaf == "o_norm":
            assert (w == 1.0).all(), name
        elif leaf.startswith("conv_"):
            assert np.abs(w).max() <= 0.5 and w.std() > 0.2, name
        elif leaf == "A_log":
            assert (np.log(1e-3) <= w).all() and (w < np.log(16.0)).all()
        elif leaf == "dt_bias":
            step = np.log1p(np.exp(w))         # softplus
            assert (step > 0.9e-3).all() and (step < 1.1e-1).all()
        elif w.size >= 2048:
            # normal(0.02): the spread of a few thousand draws to a tenth.
            assert abs(float(w.std()) / 0.02 - 1.0) < 0.1, name
            assert abs(float(w.mean())) < 0.002, name
    # A leaf left out, or drawn from another key, is no small gap.
    assert train_lm.init_gap({k: got[k] for k in list(got)[1:]}, want) \
        == float("inf")
    assert train_lm.init_gap(dict(got, head=got["head"][::-1]), want) > 0.5


@pytest.mark.parametrize("fault", ["std", "A_range", "conv_bound"])
def test_a_program_that_draws_otherwise_does_not_start_there(
        fault, monkeypatch):
    """What ``init_gap`` reads where the program's initialiser is not the
    configuration's: another spread, A from (0, 16) and not (1e-3, 16),
    the convolution's bound of another fan-in."""
    # The driver keeps a compiled initialisation for a configuration.
    monkeypatch.setattr(train_lm, "_COMPILED", {})
    if fault == "std":
        monkeypatch.setattr(hybrid_lm, "_normal_init",
                            jax.nn.initializers.normal(0.021))
    elif fault == "A_range":
        monkeypatch.setattr(
            hybrid_lm, "_a_log_init",
            lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0)),
        )
    else:
        monkeypatch.setattr(
            hybrid_lm, "_conv_init",
            lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -1.0, 1.0),
        )
    # The least of them, A's range, moves log A by 1e-3 / A: far above
    # float32's 1e-7 and still far below any tolerance one would grant.
    got, want, _ = _init_sides(1, 4)  # one linear layer has all three
    assert train_lm.init_gap(got, want) > 1e-6


@pytest.mark.parametrize("seq_len", [16, 21, 5])
def test_chunked_delta_rule_matches_the_recurrence(seq_len):
    """Lengths that are a multiple of the chunk (8), are not, and are
    shorter than one chunk; forward and all five gradients."""
    B, H, Dk, Dv = 2, 3, 8, 6

    @jax.jit
    def inputs(key):
        keys = jax.random.split(key, 6)
        k = jax.random.normal(keys[1], (B, seq_len, H, Dk))
        return (
            jax.random.normal(keys[0], (B, seq_len, H, Dk)) * Dk ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            jax.random.normal(keys[2], (B, seq_len, H, Dv)),
            -jax.random.uniform(keys[3], (B, seq_len, H), maxval=1.5),
            jax.random.uniform(keys[4], (B, seq_len, H)),
            jax.random.normal(keys[5], (B, seq_len, H, Dv)),
        )

    *args, probe = inputs(jax.random.key(seq_len))

    def both(fn):
        def probed(*a):
            out = fn(*a)
            return jnp.sum(out * probe), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            probed, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(*args)
        return out, grads

    got, got_grads = both(
        lambda *a: chunk_gated_delta_rule(*a, chunk=8)
    )
    want, want_grads = both(
        lambda *a: ref.gated_delta_recurrence(*a, block=4)
    )
    # The same sums in another order, float32.
    np.testing.assert_allclose(got, want, atol=ATOL)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_gated_attention_mixer_matches_the_reference():
    """Partial rotary, q/k norm, output gate, grouped kv, causal."""
    mixer = hybrid_lm.GatedAttentionMixer(
        num_heads=4, num_kv_heads=2, head_dim=32, rotary_dim=8,
        dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.key(0), (2, 19, 64))
    params = jax.jit(mixer.init)(jax.random.key(1), x)["params"]
    params["q_norm"]["scale"] = params["q_norm"]["scale"] + 0.3
    params["k_norm"]["scale"] = params["k_norm"]["scale"] - 0.2
    wq = params["q_proj"]["kernel"].reshape(64, 4, 64)
    p = {
        "wq": wq[..., :32], "w_gate": wq[..., 32:],
        "wk": params["k_proj"]["kernel"].reshape(64, 2, 32),
        "wv": params["v_proj"]["kernel"].reshape(64, 2, 32),
        "q_norm": params["q_norm"]["scale"],
        "k_norm": params["k_norm"]["scale"],
        "wo": params["o_proj"]["kernel"],
    }
    want = jax.jit(
        lambda p, x: ref.gated_attention(p, x, REF_CFG, query_block=8)
    )(p, x)
    apply = jax.jit(mixer.apply)
    np.testing.assert_allclose(apply({"params": params}, x), want, atol=ATOL)
    # Causal: a later token changes no earlier output.
    x2 = x.at[:, 12].add(1.0)
    out2 = apply({"params": params}, x2)
    np.testing.assert_allclose(out2[:, :12], want[:, :12], atol=ATOL)
    assert float(jnp.max(jnp.abs(out2[:, 12:] - want[:, 12:]))) > 1e-3


def _moe_layer(held, tile=8):
    return hybrid_lm.DroplessMoE(
        num_experts=16, top_k=3, expert_width=32, shared_width=32,
        held_experts=held, tile=tile, dtype=jnp.float32,
    )


def _moe_apply(layer, params, x):
    """(output, sown routing counts), compiled."""
    out, stats = jax.jit(
        lambda p, x: layer.apply({"params": p}, x,
                                 mutable=[hybrid_lm.STATS_COLLECTION])
    )(params, x)
    return out, stats[hybrid_lm.STATS_COLLECTION]


def _moe_reference(params, x, cfg=REF_CFG):
    return jax.jit(lambda p, x: ref.moe(p, x, cfg))(
        _moe_reference_params(params), x
    )


def _moe_reference_params(params):
    shared = params["shared_expert"]
    return {
        "router": params["router"]["kernel"],
        "w_gate": params["w_gate"], "w_up": params["w_up"],
        "w_down": params["w_down"],
        "shared.w_gate": shared["gate_proj"]["kernel"],
        "shared.w_up": shared["up_proj"]["kernel"],
        "shared.w_down": shared["down_proj"]["kernel"],
        "shared.gate": params["shared_expert_gate"]["kernel"],
    }


def test_moe_layer_matches_the_reference():
    layer = _moe_layer((4, 4))
    x = jax.random.normal(jax.random.key(0), (2, 13, 64))
    params = jax.jit(layer.init)(jax.random.key(1), x)["params"]
    # A router that spreads its choices (0.02-normal weights hardly do).
    params["router"]["kernel"] = params["router"]["kernel"] * 40.0
    got, sown = _moe_apply(layer, params, x)
    np.testing.assert_allclose(got, _moe_reference(params, x), atol=ATOL)
    _, top_e = ref.route(
        {"router": params["router"]["kernel"]}, x.reshape(26, 64), REF_CFG
    )
    local = int(jnp.sum((top_e >= 4) & (top_e < 8)))
    assert 0 < local < 26 * 3
    assert float(sown["local_pairs"][0]) == local


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the routed parts that the four shares
    give, plus the shared expert counted once, equal the uncut reference's
    layer output."""
    x = jax.random.normal(jax.random.key(0), (2, 11, 64))
    whole = _moe_layer((0, 16))
    params = jax.jit(whole.init)(jax.random.key(1), x)["params"]
    params["router"]["kernel"] = params["router"]["kernel"] * 40.0
    uncut = _moe_reference(params, x, dict(REF_CFG, held_experts=[0, 16]))
    t = x.reshape(22, 64)
    shared_once = ref.shared_part(_moe_reference_params(params), t)
    total = shared_once
    for first in (0, 4, 8, 12):
        share = dict(params)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = params[name][first:first + 4]
        out, _ = _moe_apply(_moe_layer((first, 4)), share, x)
        # A share's output is its routed part plus the shared expert,
        # which every holder computes alike: count it once.
        total = total + out.reshape(22, 64) - shared_once
    np.testing.assert_allclose(total.reshape(x.shape), uncut, atol=ATOL)


def test_dropless_under_imbalance():
    """A router biased so that one held expert gets most tokens loses
    none: every pair that lands here is computed, whatever the tile."""
    layer = _moe_layer((4, 4), tile=8)
    x = jnp.abs(jax.random.normal(jax.random.key(0), (2, 40, 64))) + 0.1
    params = jax.jit(layer.init)(jax.random.key(1), x)["params"]
    router = params["router"]["kernel"]
    params["router"]["kernel"] = router.at[:, 5].add(1.0)  # x > 0: expert 5 wins
    got, sown = _moe_apply(layer, params, x)
    _, top_e = ref.route(
        {"router": params["router"]["kernel"]}, x.reshape(80, 64), REF_CFG
    )
    assert int(jnp.sum(top_e == 5)) == 80  # every token chose it
    np.testing.assert_allclose(got, _moe_reference(params, x), atol=ATOL)
    assert float(sown["local_pairs"][0]) == int(
        jnp.sum((top_e >= 4) & (top_e < 8))
    )
    assert float(sown["load_max_over_mean"][0]) > 2.0
    # The plan's rows hold every local pair exactly once.
    plan = grouped_experts.make_plan(top_e, 4, 4, 8)
    # Expert 5's run of 10 tiles is walked mostly in strides.
    share = float(grouped_experts.stride_row_share(plan))
    assert share > 0.5
    assert float(sown["stride_row_share"][0]) == share
    pairs = np.asarray(plan.row_pair)
    pairs = pairs[pairs < 80 * 3]
    local = np.flatnonzero(np.asarray((top_e >= 4) & (top_e < 8)).reshape(-1))
    assert sorted(pairs.tolist()) == local.tolist()


@pytest.mark.parametrize("tokens,top_k,experts,first,held,tile", [
    (64, 3, 16, 4, 4, 8), (128, 10, 512, 0, 32, 16), (50, 2, 8, 0, 8, 4),
    (40, 4, 16, 12, 4, 8),
])
def test_the_plan_is_a_stable_sort_by_expert(
        tokens, top_k, experts, first, held, tile):
    """``make_plan`` counts; what it yields is what sorting the pairs by
    expert (stably: an expert's pairs in their own order) yields, each
    expert's run from a tile's first row, the other rows empty."""
    rng = np.random.default_rng(tokens)
    idx = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)])
    plan = grouped_experts.make_plan(jnp.asarray(idx, jnp.int32), first, held, tile)
    P = tokens * top_k
    R = grouped_experts.padded_rows(tokens, top_k, held, tile)
    flat = idx.reshape(-1) - first
    row_pair = np.full(R, P)
    pair_row = np.full(P, R)
    run_start, run_tiles, row = [], [], 0
    for e in range(held):
        pairs = np.flatnonzero(flat == e)          # in order: stable
        row_pair[row:row + len(pairs)] = pairs
        pair_row[pairs] = row + np.arange(len(pairs))
        tiles = -(-len(pairs) // tile)
        run_start.append(row)
        run_tiles.append(tiles)
        row += tiles * tile
    assert np.array_equal(plan.row_pair, row_pair)
    assert np.array_equal(plan.pair_row.reshape(-1), pair_row)
    assert np.array_equal(plan.row_token,
                          np.where(row_pair < P, row_pair // top_k, tokens))
    assert np.array_equal(plan.run_start, run_start)
    assert np.array_equal(plan.run_tiles, run_tiles)
    assert np.array_equal(plan.sizes, [(flat == e).sum() for e in range(held)])


def test_zero_centred_norm_and_cross_entropy():
    x = jax.random.normal(jax.random.key(0), (3, 5, 16)) * 3.0
    norm = hybrid_lm.ZeroCentredRMSNorm()
    params = {"scale": jnp.linspace(-0.5, 0.5, 16)}
    got = norm.apply({"params": params}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1.0 + params["scale"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(
        got, ref.zero_centred_rms_norm(x, params["scale"], 1e-6), rtol=1e-6
    )
    # w = 0 is the plain RMS norm: unit mean square.
    unit = norm.apply({"params": {"scale": jnp.zeros(16)}}, x)
    np.testing.assert_allclose(jnp.mean(unit * unit, -1), 1.0, rtol=1e-5)

    logits = jax.random.normal(jax.random.key(1), (2, 7, 11)) * 2.0
    ids = jax.random.randint(jax.random.key(2), (2, 7), 0, 11)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    want = -np.log(np.take_along_axis(probs, np.asarray(ids)[..., None], -1)).mean()
    np.testing.assert_allclose(
        get_loss("cross_entropy")(logits, ids), want, rtol=1e-6
    )
    # bfloat16 logits are widened before the softmax, not after.
    assert get_loss("cross_entropy")(logits.astype(jnp.bfloat16), ids).dtype \
        == jnp.float32


def _token_data(seed=3, vocab=97, seq_len=16):
    xt, yt, xv, yv = datagen_tokens.make_sequences(
        seed, n_train=8, n_val=2, seq_len=seq_len, vocab=vocab
    )
    return Dataset(xt, yt), Dataset(xv, yv)


def test_token_data_is_seeded_and_shifted():
    (train, val), (again, _) = _token_data(), _token_data()
    assert train.x.dtype == np.int32 and train.x.shape == (8, 16)
    np.testing.assert_array_equal(train.x, again.x)
    np.testing.assert_array_equal(train.x[:, 1:], train.y[:, :-1])
    assert not np.array_equal(train.x, _token_data(seed=4)[0].x)
    assert 0 <= train.x.min() and train.y.max() < 97 and len(val) == 2


def test_stage_data_keeps_integers_and_leaves_floats_as_they_were():
    train, val = _token_data()
    data = stage_data(train, val, 2, jnp.bfloat16)
    assert data.x_train.dtype == data.y_train.dtype == jnp.int32
    assert data.x_val.dtype == jnp.int32
    np.testing.assert_array_equal(data.x_train, train.x)
    rng = np.random.default_rng(0)
    ftrain = Dataset(rng.standard_normal((8, 4, 3)).astype(np.float32),
                     rng.standard_normal((8, 1)).astype(np.float32))
    fval = Dataset(rng.standard_normal((3, 4, 3)).astype(np.float32),
                   rng.standard_normal((3, 1)).astype(np.float32))
    staged = stage_data(ftrain, fval, 2, jnp.bfloat16)
    assert staged.x_train.dtype == jnp.bfloat16
    assert staged.y_train.dtype == jnp.float32
    np.testing.assert_array_equal(
        staged.x_train, jnp.asarray(ftrain.x, jnp.bfloat16)
    )
    np.testing.assert_array_equal(staged.y_val[:3], fval.y)


# -- the benchmark's driver on a tiny cell -----------------------------------

TINY_LIMITS = {
    "init_gap": 0.0, "loss_rerun_gap": 0.0, "loss_e0_gap": 1e-4, "val_e0_gap": 1e-4,
    "param_change_gap_med": 1e-3, "param_change_gap_p90": 1e-2,
    "param_change_half_ratio_med": 0.5,
}


def _tiny_cell():
    """Two layers, one of each kind, through the ``train_lm`` driver."""
    from benchmark.run import Cell

    trial = dict(TRIAL, num_layers=2, full_attention_interval=2,
                 optimizer="adam", loss_function="cross_entropy",
                 learning_rate=1e-2, weight_decay=0.0, checkpoint_freq=0)
    return Cell(
        name="tiny_lm", chips=1, config_name="tiny-hybrid",
        config=dict(REF_CFG, num_hidden_layers=2, full_attention_interval=2,
                    trial=trial),
        traffic_name="tiny", traffic={
            "driver": "train_lm", "seq_len": 16, "batch_size": 2,
            "steps_per_epoch": 2, "val_sequences": 2, "num_epochs": 1000,
            "trace_seconds": 1, "reference_block_rows": 1, "control": "bf16",
            "limits": dict(TINY_LIMITS),
        },
        end_to_end=[{"name": "train_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[],
    )


@pytest.fixture
def fresh_programs():
    tune.clear_program_cache()
    yield
    tune.clear_program_cache()


def test_one_trial_through_tune_run_is_correct_and_repeats(
        tmp_path, fresh_programs):
    """The tiny cell end to end: three ``tune.run(train_regressor)`` calls
    on integer data with ``cross_entropy`` (the state epoch with its
    checkpoint, the warm-up, the window) report the same first losses to
    the bit, the checkpoint is written and read back, and its parameters'
    change is the reference's."""
    import time

    from benchmark.run import run_cell

    result = run_cell(
        _tiny_cell(), seed=2_147_483_659, seconds=0.5, traced=False,
        devices=jax.devices()[:1], work_dir=str(tmp_path),
        process_start=time.time(),
    )
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert result["checks"]["loss_rerun_gap"]["value"] == 0.0
    assert result["checks"]["init_gap"]["value"] == 0.0
    assert set(TINY_LIMITS) <= set(result["checks"])


def test_the_control_and_an_unchanged_state_are_not_correct(fresh_programs):
    """The driver's ``readings`` on one seed: sound within the limits; the
    reference in the precision below (bfloat16 operands under this tiny
    cell's float32), a state left unchanged and the program itself with
    half of every batch left out of its loss each outside one."""
    import tempfile

    from benchmark.reference import regressor
    from benchmark.run import Run, judge

    cell = _tiny_cell()

    def make_run(seed):
        return Run(cell=cell, seed=seed, seconds=0.0, traced=False,
                   devices=jax.devices()[:1], peaks=None,
                   work_dir=tempfile.mkdtemp(prefix="dml_lm_"))

    verdicts = {}
    for seed, what, numbers in train_lm.readings(
        make_run, [5], 1, regressor.bf16, broken=1
    ):
        numbers.pop("loss_rerun_gap", None)
        verdicts[what] = [name for name, _, _, ok
                          in judge(numbers, TINY_LIMITS) if not ok]
    assert verdicts["sound"] == []
    assert verdicts["control"], "bfloat16 operands passed every limit"
    assert "param_change_gap_med" in verdicts["unchanged"]
    # Nearer to the reference with a row left out than to the whole one.
    assert "param_change_half_ratio_med" in verdicts["half_batch"]


def test_the_cell_refuses_a_program_without_its_family():
    cfg = train_lm.trial_config(type("R", (), {
        "cell": _tiny_cell(), "seed": 1})())
    train_lm.require_program(cfg)  # this program has both
    with pytest.raises(SystemExit, match="no model 'absent'"):
        train_lm.require_program(dict(cfg, model="absent"))
    with pytest.raises(SystemExit, match="no loss_function"):
        train_lm.require_program(dict(cfg, loss_function="absent"))


def test_token_loss_under_streaming_input_is_refused_at_build():
    """The prefetch ring stages every array in the compute dtype and its
    evaluator is the regression one: a language model there would train on
    ids cast to floats, so the trial says so before it builds anything."""
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 97, size=(8, 17)).astype(np.int32)
    data = Dataset(tokens[:, :-1], tokens[:, 1:])
    config = dict(TRIAL, loss_function="cross_entropy", learning_rate=1e-3,
                  num_epochs=1, batch_size=4, input_mode="streaming")
    with tune.standalone(), pytest.raises(
        ValueError, match="cross_entropy.*input_mode='streaming'"
    ):
        tune.train_regressor(config, train_data=data, val_data=data)


def test_snapshot_host_fallback_writes_the_same_bytes(tmp_path, monkeypatch):
    tree = {
        "params": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones(4)},
        "opt_state": {"count": jnp.int32(3)}, "epoch": 0, "rng_impl": "",
    }

    def write(name, room):
        monkeypatch.setattr(
            AsyncCheckpointWriter, "_device_has_room_for",
            staticmethod(lambda leaves: room),
        )
        writer = AsyncCheckpointWriter()
        path = writer.submit(str(tmp_path / name), tree)
        writer.wait(path)
        writer.close()
        with open(path, "rb") as f:
            return f.read()

    on_device, on_host = write("a.msgpack", True), write("b.msgpack", False)
    assert on_device == on_host and len(on_host) > 0


def test_device_room_reads_the_allocator(monkeypatch):
    """No room where the tree's bytes exceed what the device has left."""
    x = jnp.ones((1024,), jnp.float32)  # 4,096 bytes

    class Device:
        def __init__(self, in_use):
            self.in_use = in_use

        def memory_stats(self):
            return {"bytes_limit": 10_000, "bytes_in_use": self.in_use}

    class Sharding:
        def __init__(self, *devices):
            self.device_set = set(devices)

    class Shard:
        def __init__(self, device):
            self.device, self.data = device, x

    room = AsyncCheckpointWriter._device_has_room_for
    assert room([x, 3, "s"])  # the CPU reports no statistics
    for in_use, want in ((8_000, False), (1_000, True)):
        device = Device(in_use)
        monkeypatch.setattr(
            type(x), "sharding", property(lambda self, d=device: Sharding(d))
        )
        assert room([x]) is want
        # Two leaves are counted together.
        assert room([x, x]) is (want and 2 * 4096 <= 10_000 - in_use)
    # Over several devices each is asked for its own shards.
    full, roomy = Device(8_000), Device(1_000)
    monkeypatch.setattr(
        type(x), "sharding", property(lambda self: Sharding(full, roomy))
    )
    monkeypatch.setattr(
        type(x), "addressable_shards",
        property(lambda self: [Shard(full), Shard(roomy)]),
    )
    assert room([x]) is False

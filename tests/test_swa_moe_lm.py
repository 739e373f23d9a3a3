"""The sliding-window / global attention language model (family
``swa_moe_lm``) against its plain reference, and the flash kernels' window
band, at tiny widths on the CPU, with seeded random weights.

The program runs in float32 here, so it and the reference differ by the
order of their sums only; each tolerance says what it allows for.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.drivers import train_swa_lm
from benchmark.reference import swa_moe_lm as ref
from distributed_machine_learning_tpu.models import build_model, hybrid_lm, swa_lm
from distributed_machine_learning_tpu.ops import grouped_experts
from distributed_machine_learning_tpu.ops.losses import get_loss
from distributed_machine_learning_tpu.ops.pallas_attention import flash_attention

# The published key names at widths a CPU test can afford: a global layer
# and a window layer of 5 keys, 7 query heads on 1 key-value head, 16
# experts of which ids 4-7 are held, top-6.
REF_CFG = {
    "num_hidden_layers": 2, "hidden_size": 64, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "num_attention_heads": 7, "num_key_value_heads": 1,
    "head_dim": 16, "rope_theta": 1.5e6, "sliding_window_size": 5,
    "sliding_window_layout": [0, 1, 1, 1], "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 6,
    "published": {"moe_num_primary_experts": 16}, "held_experts": [4, 4],
}
TRIAL = {
    "model": "swa_moe_lm", "vocab_size": 97, "num_layers": 2, "d_model": 64,
    "num_heads": 7, "num_kv_heads": 1, "head_dim": 16, "rope_theta": 1.5e6,
    "sliding_window": 5, "window_layout": [0, 1, 1, 1], "norm_eps": 1e-6,
    "num_experts": 16, "top_k": 6, "expert_width": 32, "held_experts": [4, 4],
    "expert_tile": 8, "compute_dtype": "float32",
}
# Float32 sums in another order: a few units in the seventh digit a sum,
# over two layers and a few hundred terms.
ATOL = 2e-5


def _spread(params):
    """Weights at which a wrong formula shows: norm scales off 1 and a
    router that spreads its choices over the experts (0.02-normal weights
    hardly do)."""
    def move(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return a + 0.1 * jnp.arange(a.size, dtype=a.dtype).reshape(a.shape) / a.size
        if "router" in name:
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
    as_ref = train_swa_lm.to_reference(params)
    (want_loss, want_logits), want_grads = jax.jit(
        jax.value_and_grad(reference_loss, has_aux=True)
    )(as_ref)
    return ((logits, loss, train_swa_lm.to_reference(grads)),
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
    assert ref.layer_kinds(REF_CFG) == ["global", "window"]


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


@pytest.mark.parametrize("block_rows,rows_used,positions_used,ahead", [
    (2, None, None, False), (1, None, None, True), (2, 1, None, False),
    (1, None, 10, False),
])
def test_the_references_layerwise_step_is_the_whole_models(
        stack, block_rows, rows_used, positions_used, ahead):
    """``make_step`` takes the chain rule a layer at a time: its loss and
    its Adam step are those of ``jax.value_and_grad`` over the whole
    model, with the batch whole or in blocks of rows, with a row or the
    last positions of every row left out, compiled at the first call or
    ahead of it."""
    from benchmark.reference.regressor import adam_init, adam_update

    _, _, (params, tokens, targets) = stack
    used = rows_used or 2
    kept = positions_used or tokens.shape[1]

    def whole_loss(p):
        nll = ref.token_losses(p, tokens, targets, REF_CFG)
        return jnp.sum(nll[:used, :kept]) / (used * kept)

    want_loss, grads = jax.jit(jax.value_and_grad(whole_loss))(params)
    want, _ = adam_update(params, grads, adam_init(params), 1e-2, 0.0, 100)
    step = ref.make_step(REF_CFG, 2, block_rows, 100, rows_used=rows_used,
                         positions_used=positions_used,
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
    return (train_swa_lm.program_init(trial, np.zeros((1, 4), np.int32)),
            train_swa_lm.reference_init(trial, cfg), cfg)


def test_the_program_starts_from_the_references_weights():
    """The program's initialisation from a trial's seed, renamed by
    ``to_reference``, is the reference's own ``init_params`` to the bit in
    every leaf (the same generator bits through the same float32
    operations: no tolerance); and the reference's draws are what the
    configuration's ``assumed`` block says."""
    got, want, cfg = _init_sides()
    assert train_swa_lm.init_gap(got, want) == 0.0
    assert {k: v.shape for k, v in want.items()} == ref.parameter_shapes(cfg)
    for name, w in want.items():
        if name.endswith("norm"):
            assert (w == 1.0).all(), name
        elif w.size >= 2048:
            # normal(0.02): the spread of a few thousand draws to a tenth.
            assert abs(float(w.std()) / 0.02 - 1.0) < 0.1, name
            assert abs(float(w.mean())) < 0.002, name
    # A leaf left out, or drawn from another key, is no small gap.
    assert train_swa_lm.init_gap({k: got[k] for k in list(got)[1:]}, want) \
        == float("inf")
    assert train_swa_lm.init_gap(dict(got, head=got["head"][::-1]), want) > 0.5


def test_a_program_that_draws_otherwise_does_not_start_there(monkeypatch):
    monkeypatch.setattr(train_swa_lm.train_lm, "_COMPILED", {})
    monkeypatch.setattr(swa_lm, "_normal_init", jax.nn.initializers.normal(0.021))
    monkeypatch.setattr(hybrid_lm, "_normal_init",
                        jax.nn.initializers.normal(0.021))
    got, want, _ = _init_sides(num_layers=1)
    assert train_swa_lm.init_gap(got, want) > 1e-3


@pytest.mark.parametrize("window", [None, 5])
def test_attention_matches_the_reference_and_reads_its_band(window):
    """Both kinds of layer: rotary positions on the whole head and the
    band of ``window`` keys, or neither; 7 query heads on one key-value
    head.  A change at position 8 moves no output before it, and in the
    window layer none from 8 + window on."""
    attention = swa_lm.SWAAttention(7, 1, 16, window, 1.5e6, jnp.float32)
    a = jax.random.normal(jax.random.key(0), (2, 19, 64))
    params = jax.jit(attention.init)(jax.random.key(1), a)["params"]
    p = {"wq": params["q_proj"]["kernel"], "wk": params["k_proj"]["kernel"],
         "wv": params["v_proj"]["kernel"], "wo": params["o_proj"]["kernel"]}
    kind = "global" if window is None else "window"
    want = jax.jit(lambda p, a: ref.attention(p, a, REF_CFG, kind,
                                              query_block=8))(p, a)
    apply = jax.jit(attention.apply)
    got = apply({"params": params}, a)
    np.testing.assert_allclose(got, want, atol=ATOL)
    moved = jnp.max(jnp.abs(apply({"params": params}, a.at[:, 8].add(1.0)) - got),
                    axis=(0, 2))
    assert float(jnp.max(moved[:8])) == 0.0
    if window is None:
        assert float(jnp.min(moved[8:])) > 1e-4
    else:
        assert float(jnp.min(moved[8:8 + window])) > 1e-4
        assert float(jnp.max(moved[8 + window:])) == 0.0


# -- the flash kernels' window band, interpreted -----------------------------


def _masked(q, k, v, window):
    """Plain softmax attention under the band ``0 <= t - j < window``."""
    B, S, H, D = q.shape
    kr, vr = (jnp.repeat(t, H // k.shape[2], axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr, precision="highest") * D ** -0.5
    i = jnp.arange(S)
    keep = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr, precision="highest")


@pytest.mark.parametrize("window,heads,kv_heads", [
    (5, 7, 1),      # a window under a block, a group of 7
    (21, 2, 2),     # not a multiple of a block, groups of 1
    (64, 7, 1),     # the whole sequence: causal attention
    (100, 2, 2),    # more than the sequence
])
def test_the_window_kernels_match_plain_masked_attention(window, heads, kv_heads):
    """The forward and both gradients of the kernels (interpreted, blocks
    of 16 over 64 positions, so that a q block meets a band of several kv
    blocks and skips the rest) against dense attention under the band.
    float32 throughout: the tolerances are of a sum's order."""
    ks = jax.random.split(jax.random.key(window + heads), 4)
    q = jax.random.normal(ks[0], (1, 64, heads, 32))
    k, v = (jax.random.normal(key, (1, 64, kv_heads, 32)) for key in ks[1:3])
    w = jax.random.normal(ks[3], q.shape)
    flash = functools.partial(flash_attention, scale=None, causal=True,
                              block_q=16, block_k=16, interpret=True,
                              window=window)
    got = jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(_masked(q, k, v, window) * w),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-5 * float(jnp.max(jnp.abs(r))))
    if window >= 64:
        causal = flash_attention(q, k, v, None, True, 16, 16, True)
        np.testing.assert_allclose(flash(q, k, v), causal, atol=1e-6)


def test_a_window_without_causal_is_refused():
    q = jnp.zeros((1, 32, 2, 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, None, False, 16, 16, True, 8)


# -- the expert layer ----------------------------------------------------------


def test_the_relu_experts_backward_is_the_gradient_of_a_loop():
    """``routed_experts`` with ReLU: its hand-written backward against
    ``jax.grad`` of a plain loop over the experts, every input."""
    T, d, f, E, K = 40, 16, 8, 4, 2
    ks = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(ks[0], (T, d))
    wg, wu = (jax.random.normal(k, (E, d, f)) * 0.3 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, f, d)) * 0.3
    top_e = jax.random.randint(ks[4], (T, K), 0, E + 2)   # some ids absent
    weights = jax.nn.softmax(jax.random.normal(ks[5], (T, K)), -1)
    plan = grouped_experts.make_plan(top_e, 0, E, 8)
    dy = jax.random.normal(jax.random.key(9), (T, d))

    def program(x, weights, wg, wu, wd):
        return jnp.sum(dy * grouped_experts.routed_experts(
            x, weights, wg, wu, wd, plan, 8, "relu"))

    def loop(x, weights, wg, wu, wd):
        y = 0.0
        for e in range(E):
            gate = jnp.sum(jnp.where(top_e == e, weights, 0.0), -1)
            out = (jax.nn.relu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
            y = y + gate[:, None] * out
        return jnp.sum(dy * y)

    args = (x, weights, wg, wu, wd)
    got = jax.jit(jax.grad(program, argnums=tuple(range(5))))(*args)
    want = jax.jit(jax.grad(loop, argnums=tuple(range(5))))(*args)
    np.testing.assert_allclose(program(*args), loop(*args), rtol=1e-5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5 * float(jnp.max(jnp.abs(w))))


def _moe_layer(held):
    return hybrid_lm.DroplessMoE(
        num_experts=64, top_k=6, expert_width=32, shared_width=0,
        held_experts=held, tile=8, dtype=jnp.float32,
        router=swa_lm.Router(64, parent=None), activation="relu",
    )


def _moe_sides(held):
    """(the layer, its spread parameters, b, a, the reference's leaves
    under its ``moe.`` names)."""
    layer = _moe_layer(held)
    b, a = (jax.random.normal(jax.random.key(i), (2, 29, 64)) for i in (0, 5))
    params = _spread(jax.jit(lambda b, a: layer.init(
        jax.random.key(1), b, route_from=a))(b, a)["params"])
    as_ref = {"router": params["router"]["kernel"],
              **{name: params[name] for name in ("w_gate", "w_up", "w_down")}}
    return layer, params, b, a, as_ref


def test_the_four_shares_add_up_to_the_uncut_layer():
    """64 experts in 4 shares of 16 (the deployment's four chips): what
    the shares give adds up to the uncut reference's layer output; there
    is no shared expert to count once."""
    whole, params, b, a, as_ref = _moe_sides((0, 64))
    cfg = dict(REF_CFG, held_experts=[0, 64],
               published={"moe_num_primary_experts": 64})
    uncut = jax.jit(lambda p, a, b: ref.moe(p, a, b, cfg))(as_ref, a, b)
    total = jnp.zeros_like(uncut)
    for first in (0, 16, 32, 48):
        share = dict(params)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = params[name][first:first + 16]
        part = jax.jit(lambda p, b, a, first=first: _moe_layer(
            (first, 16)).apply({"params": p}, b, route_from=a))(share, b, a)
        assert float(jnp.max(jnp.abs(part))) > 1e-3, first
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=ATOL)


def test_the_router_reads_the_normed_input_not_the_experts_input():
    """The expert layer told ``route_from`` routes by that tensor: it is
    the reference's layer with the router reading a, and not the one that
    routes by b = norm(h), the tensor the experts transform."""
    layer, params, b, a, as_ref = _moe_sides((0, 16))
    cfg = dict(REF_CFG, held_experts=[0, 16],
               published={"moe_num_primary_experts": 64})
    got = jax.jit(lambda p, b, a: layer.apply({"params": p}, b, route_from=a))(
        params, b, a)
    np.testing.assert_allclose(
        got, jax.jit(lambda p, a, b: ref.moe(p, a, b, cfg))(as_ref, a, b),
        atol=ATOL)
    by_b = jax.jit(lambda p, b: ref.moe(p, b, b, cfg))(as_ref, b)
    # Other choices for most tokens: a gap of the output's own order.
    assert float(jnp.max(jnp.abs(got - by_b))) > 0.3 * float(jnp.max(jnp.abs(got)))


# -- the other families' programs ---------------------------------------------

# The epoch programs of the two other language-model families at a tiny
# size, lowered on a CPU (jax 0.9.0; ``as_text()`` of
# ``make_epoch_fn`` under jit, shapes only) from the tree whose routed
# experts walk each held expert's run in strides (``ops/grouped_experts.py
# _walk``): the window, the activation and the router's input are
# arguments whose defaults leave them as they were.
PARENT_EPOCH_SHA256 = {
    # Pinned again since the delta rule repeats q and k to the value heads
    # itself on XLA's path: the operations reordered, the numbers the same
    # to the bit.
    "gated_hybrid_lm":
        "e1e84faf7a1e3be2e239649eb45503954bf4c15241b9c33d0561b89b401b979d",
    "cca_moe_lm":
        "615794e2a5668db090cceabf0b6bb3376966777b0d8016856b75f9e597e2c4f1",
}


def _tiny_trial(family):
    from test_cca_moe_lm import HYBRID_TRIAL, TRIAL as CCA_TRIAL

    return {"gated_hybrid_lm": HYBRID_TRIAL, "cca_moe_lm": CCA_TRIAL}[family]


@pytest.mark.parametrize("family", sorted(PARENT_EPOCH_SHA256))
def test_the_other_families_epoch_programs_are_the_parents(family):
    from distributed_machine_learning_tpu.tune._regression_program import (
        make_epoch_fn,
    )

    model = build_model(_tiny_trial(family))
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


def test_a_window_call_counts_the_blocks_its_band_walks_and_skips():
    """At 64 positions in blocks of 16 and a window of 21 each q block
    meets at most three kv blocks: 1 + 2 + 3 + 3 = 9 of the 16 a grid,
    over 2 column blocks (heads 7 and 1 at D 32: one block of 7 heads;
    batch 2)."""
    from distributed_machine_learning_tpu import obs

    registry = obs.get_registry()
    before = {n: registry.get("flash.window_" + n)
              for n in ("calls", "live_kv_blocks", "skipped_kv_blocks")}
    q = jnp.zeros((2, 64, 7, 32))
    k = jnp.zeros((2, 64, 1, 32))
    flash_attention(q, k, k, None, True, 16, 16, True, 21)
    after = {n: registry.get("flash.window_" + n) - v for n, v in before.items()}
    assert after == {"calls": 1, "live_kv_blocks": 2 * 9,
                     "skipped_kv_blocks": 2 * (16 - 9)}

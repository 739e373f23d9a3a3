"""``ops/grouped_experts.py``'s walk over each held expert's run against a
plain per-pair reference, at tile 8 (a stride is ``STRIDE_TILES`` tiles of
8 rows), in float32 on the CPU.

The routings put runs on both sides of a stride's boundary, leave held
experts without pairs, and send pairs to experts another chip holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_machine_learning_tpu.ops import grouped_experts

TILE = 8
D, F = 16, 8


def _top1(sizes, first, rng):
    """Top-1 choices that give held expert ``e`` exactly ``sizes[e]``
    tokens, in a shuffled order."""
    ids = np.concatenate([np.full(n, first + e) for e, n in enumerate(sizes)])
    return rng.permutation(ids)[:, None]


def _distinct(tokens, k, experts, rng):
    """Each token's ``k`` distinct experts, drawn uniformly."""
    return np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])


# name -> (experts E, first held id, held count, choices [T, K] from a rng)
ROUTINGS = {
    # 40 tokens on one expert: a run of 5 tiles, the other runs empty.
    "one_expert": (4, 0, 4, lambda rng: np.full((40, 1), 2)),
    "uniform": (4, 0, 4, lambda rng: _distinct(64, 2, 4, rng)),
    "held_without_pairs": (
        6, 0, 6, lambda rng: rng.choice([1, 4], size=(50, 1))),
    # Runs of 1, 3, 4, 5, 8 and 9 tiles, three ending in a part-filled tile.
    "runs_1_3_4_5_8_9": (
        6, 0, 6, lambda rng: _top1([8, 20, 32, 37, 64, 65], 0, rng)),
    "top1_off_chip": (8, 2, 4, lambda rng: rng.integers(0, 8, size=(90, 1))),
    "topk_off_chip": (16, 4, 4, lambda rng: _distinct(60, 3, 16, rng)),
}


def _case(name):
    E, first, held, choose = ROUTINGS[name]
    rng = np.random.default_rng(sorted(ROUTINGS).index(name))
    top_e = jnp.asarray(choose(rng), jnp.int32)
    T, K = top_e.shape
    ks = jax.random.split(jax.random.key(T * K), 5)
    x = jax.random.normal(ks[0], (T, D))
    wg, wu = (jax.random.normal(k, (held, D, F)) * 0.3 for k in ks[1:3])
    wd = jax.random.normal(ks[3], (held, F, D)) * 0.3
    weights = jax.nn.softmax(jax.random.normal(ks[4], (T, K)), -1)
    return top_e, first, held, (x, weights, wg, wu, wd)


def _per_pair(top_e, first, held, activation):
    """``y`` as a sum over the pairs, each with its expert's weights
    gathered densely: no plan, no rows, no walk."""
    act = grouped_experts.ACTIVATIONS[activation][0]

    def routed(x, weights, wg, wu, wd):
        y = jnp.zeros(x.shape, jnp.float32)
        for j in range(top_e.shape[1]):
            local = top_e[:, j] - first
            here = (local >= 0) & (local < held)
            e = jnp.clip(local, 0, held - 1)
            g = jnp.einsum("td,tdf->tf", x, wg[e])
            u = jnp.einsum("td,tdf->tf", x, wu[e])
            out = jnp.einsum("tf,tfd->td", act(g) * u, wd[e])
            y = y + jnp.where(here, weights[:, j], 0.0)[:, None] * out
        return y

    return routed


@pytest.mark.parametrize("activation", sorted(grouped_experts.ACTIVATIONS))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_walk_matches_a_per_pair_reference(routing, activation):
    """The value and all five cotangents (``x``, ``weights`` and the three
    weight stacks) of ``routed_experts`` against ``jax.grad`` of the
    per-pair sum."""
    top_e, first, held, args = _case(routing)
    plan = grouped_experts.make_plan(top_e, first, held, TILE)
    dy = jax.random.normal(jax.random.key(9), args[0].shape)

    def program(*a):
        return jnp.sum(dy * grouped_experts.routed_experts(
            *a, plan, TILE, activation))

    reference = _per_pair(top_e, first, held, activation)

    def plain(*a):
        return jnp.sum(dy * reference(*a))

    every = tuple(range(5))
    got_y = jax.jit(lambda *a: grouped_experts.routed_experts(
        *a, plan, TILE, activation))(*args)
    np.testing.assert_allclose(got_y, jax.jit(reference)(*args),
                               atol=1e-5 * float(jnp.max(jnp.abs(got_y))))
    got = jax.jit(jax.grad(program, argnums=every))(*args)
    want = jax.jit(jax.grad(plain, argnums=every))(*args)
    for name, g, w in zip(("x", "weights", "w_gate", "w_up", "w_down"),
                          got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, atol=1e-5 * max(scale, 1e-6),
                                   err_msg=name)
    if routing == "held_without_pairs":
        # An expert that no pair reached gets a gradient of nought.
        for g in got[2:]:
            assert float(jnp.max(jnp.abs(g[jnp.asarray([0, 2, 3, 5])]))) == 0.0


@pytest.mark.parametrize("tiles", [1, 2, 3, 4, 5, 7, 8, 9, 16])
def test_one_runs_stride_row_share(tiles):
    """A run of at most ``STRIDE_TILES - 1`` tiles takes no stride; a
    longer one computes all but its remainder in strides."""
    rng = np.random.default_rng(tiles)
    plan = grouped_experts.make_plan(
        jnp.asarray(_top1([0, tiles * TILE - 3], 0, rng), jnp.int32),
        0, 2, TILE)
    assert plan.run_tiles.tolist() == [0, tiles]
    share = float(grouped_experts.stride_row_share(plan))
    n = grouped_experts.STRIDE_TILES
    assert share == pytest.approx(
        0.0 if tiles < n else (tiles - tiles % n) / tiles, rel=1e-6)


def test_stride_row_share_is_over_all_the_rows_in_use():
    """Runs of 1, 3, 4, 5, 8 and 9 tiles (30 in all): strides cover all
    but each run's remainder (26 at strides of 2 tiles, 24 at 4); no pair,
    no row in use, share nought."""
    rng = np.random.default_rng(0)
    plan = grouped_experts.make_plan(
        jnp.asarray(_top1([8, 20, 32, 37, 64, 65], 0, rng), jnp.int32),
        0, 6, TILE)
    runs = [1, 3, 4, 5, 8, 9]
    assert plan.run_tiles.tolist() == runs
    n = grouped_experts.STRIDE_TILES
    assert float(grouped_experts.stride_row_share(plan)) == pytest.approx(
        sum(t - t % n for t in runs) / sum(runs))
    empty = grouped_experts.make_plan(jnp.full((5, 1), 9, jnp.int32), 0, 6, TILE)
    assert float(grouped_experts.stride_row_share(empty)) == 0.0

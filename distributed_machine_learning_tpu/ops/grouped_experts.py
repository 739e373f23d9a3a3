"""Dropless routed experts: order the token-expert pairs by expert, then a
grouped matrix product over the experts held here.

A top-k router sends every token to ``k`` of ``num_experts`` experts; this
chip holds ``held`` of them (ids ``first .. first + held - 1``).  The pairs
that land on a held expert are ordered by expert, and each expert's run is
padded to whole tiles of ``tile`` rows.  The product walks the held experts
in turn, each expert's weights sliced once, and runs the gated feed-forward
``W_down(act(W_gate x) * W_up x)`` (``act`` SiLU or ReLU: ``ACTIVATIONS``)
over its run in strides of ``STRIDE_TILES`` tiles while that many are
left, then a tile at a time.  No expert has a capacity and no pair is
dropped: the buffers are sized for the worst routing (every token's
``min(k, held)`` choices land here), the work is what the routing asks.
What the absent experts would add is left out.

Shapes are static and the trip counts are not, so the walk is ``while``
loops on the device and its backward is written by hand
(``jax.custom_vjp``): the same walk again, recomputing a stride's
activations.  An expert's three weight gradients accumulate in float32 in
the walk's carry and leave it once an expert.  That carry is read and
written once a stride, so a stride of two tiles moves half of the
accumulator's bytes a row that a tile would, and its products contract
over 512 rows at the 256-row tile.  Everything outside the walk is
cumulative sums and gathers, and one scatter of the pairs' indices into
their rows (``make_plan``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

DEFAULT_TILE = 256
# Tiles a stride takes while a run has that many left (512 rows at the
# default tile); the run's last ``< STRIDE_TILES`` tiles go one at a time.
# Two timed faster than four or eight on a v5e at all three LM cells'
# shapes, the routed experts alone (PERF.md, section 6).
STRIDE_TILES = 2


def _silu(g):
    sg = jax.nn.sigmoid(g)
    return g * sg, lambda: sg * (1.0 + g * (1.0 - sg))


def _relu(g):
    return jnp.maximum(g, 0.0), lambda: (g > 0).astype(g.dtype)


# The gate's activation by name: the forward's function, and for the
# backward (a float32 gate) its value with a thunk of its slope.
ACTIVATIONS = {
    "silu": (jax.nn.silu, _silu),
    "relu": (jax.nn.relu, _relu),
}


class Plan(NamedTuple):
    """Where each pair's row lies, what each row holds, and each held
    expert's run of rows."""

    row_token: jnp.ndarray    # [R] token of each padded row; T = the zero row
    row_pair: jnp.ndarray     # [R] flat pair (t * K + j); T * K = no pair
    run_start: jnp.ndarray    # [held] first padded row of each expert's run
    run_tiles: jnp.ndarray    # [held] tiles in each expert's run
    pair_row: jnp.ndarray     # [T, K] padded row of each pair; R = the zero row
    sizes: jnp.ndarray        # [held] pairs on each held expert


def padded_rows(num_tokens: int, top_k: int, held: int, tile: int) -> int:
    """Rows of the worst routing: every token's ``min(k, held)`` choices
    land here, and every expert's run ends in a partly filled tile."""
    worst = num_tokens * min(top_k, held) + held * (tile - 1)
    return -(-worst // tile) * tile


def make_plan(expert_idx, first: int, held: int, tile: int) -> Plan:
    """The routing plan for ``expert_idx`` [T, K] (global expert ids).

    A pair's row is its expert's first padded row plus the number of
    earlier pairs of that expert: a counting sort over the ``held``
    experts, which keeps the pairs of an expert in their order.  (A sort
    of the T * K keys gives the same plan; each of its two sorts takes the
    chip's compiler 20 s at 163,840 keys, in every layer and again in its
    rematerialisation.)"""
    T, K = expert_idx.shape
    P = T * K
    R = padded_rows(T, K, held, tile)
    local = expert_idx.astype(jnp.int32) - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(P)
    here = key[:, None] == jnp.arange(held)[None, :]           # [P, held]
    count = jnp.cumsum(here, axis=0, dtype=jnp.int32)
    sizes = count[-1]
    earlier = jnp.sum(jnp.where(here, count - 1, 0), axis=1)   # same expert
    tiles = -(-sizes // tile)
    padded = tiles * tile
    pstarts = jnp.cumsum(padded) - padded
    pairs = jnp.arange(P, dtype=jnp.int32)
    row = jnp.append(pstarts, 0)[key] + earlier
    pair_row = jnp.where(key < held, row, R)
    # The one scatter: every pair held writes its index into its own row;
    # the others aim past the end, each at a place of its own, and drop.
    row_pair = jnp.full((R,), P, jnp.int32).at[
        jnp.where(key < held, row, R + pairs)
    ].set(pairs, mode="drop", unique_indices=True)
    return Plan(
        row_token=jnp.where(row_pair < P, row_pair // K, T),
        row_pair=row_pair,
        run_start=pstarts,
        run_tiles=tiles,
        pair_row=pair_row.reshape(T, K),
        sizes=sizes,
    )


def stride_row_share(plan: Plan):
    """The share of the rows in use that the walk computes in whole
    strides (0 where no run has ``STRIDE_TILES`` tiles)."""
    strided = plan.run_tiles // STRIDE_TILES * STRIDE_TILES
    return jnp.sum(strided) / jnp.maximum(jnp.sum(plan.run_tiles), 1)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _walk(plan: Plan, tile: int, stacks, step, carry, acc=()):
    """Each held expert's run in turn, its slices of the weight ``stacks``
    cut once: ``step(w, row0, rows, carry, acc) -> (carry, acc)`` on rows
    ``[row0, row0 + rows)``, strides of ``STRIDE_TILES`` tiles while that
    many are left, then single tiles (``rows`` is static, so ``step`` is
    traced twice).  An expert with no pairs takes no step.  ``carry`` runs
    through the walk; ``acc`` starts from the value given at every expert
    and leaves stacked, ``[held, ...]``.  Returns ``(carry, acc)``."""
    stride = STRIDE_TILES * tile

    def expert(carry, run):
        w, start, n = run
        strides = n // STRIDE_TILES

        def loop(count, first, rows, state):
            return jax.lax.fori_loop(
                0, count, lambda i, s: step(w, first + i * rows, rows, *s),
                state,
            )

        state = loop(strides, start, stride, (carry, acc))
        return loop(n - strides * STRIDE_TILES, start + strides * stride,
                    tile, state)

    return jax.lax.scan(
        expert, carry, (stacks, plan.run_start, plan.run_tiles)
    )


def _rows(plan: Plan, row0, rows: int, *row_arrays):
    """Rows ``[row0, row0 + rows)`` of the plan, taken from each of
    ``row_arrays`` by their tokens."""
    tok = jax.lax.dynamic_slice(plan.row_token, (row0,), (rows,))
    return [a[tok] for a in row_arrays]


def _hidden(xt, wg_e, wu_e):
    g = _dot(xt, wg_e, ((1,), (0,)))
    u = _dot(xt, wu_e, ((1,), (0,)))
    return g, u


def _forward(x, weights, w_gate, w_up, w_down, plan: Plan, tile: int,
             activation: str = "silu"):
    act = ACTIVATIONS[activation][0]
    T, d = x.shape
    R = plan.row_token.shape[0]
    dtype = x.dtype
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), dtype)])
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))

    def step(w, row0, rows, out_rows, acc):
        wg_e, wu_e, wd_e = w
        (xt,) = _rows(plan, row0, rows, x_pad)
        g, u = _hidden(xt, wg_e, wu_e)
        h = (act(g) * u).astype(dtype)
        o = _dot(h, wd_e, ((1,), (0,))).astype(dtype)
        return jax.lax.dynamic_update_slice(out_rows, o, (row0, 0)), acc

    out_rows, _ = _walk(
        plan, tile, (wg, wu, wd), step, jnp.zeros((R + 1, d), dtype)
    )
    # One choice at a time: all K at once would be a [T, K, d] float32.
    weights = weights.astype(jnp.float32)
    y = jnp.zeros((T, d), jnp.float32)
    for j in range(weights.shape[1]):
        y = y + weights[:, j, None] * out_rows[plan.pair_row[:, j]].astype(
            jnp.float32
        )
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def routed_experts(x, weights, w_gate, w_up, w_down, plan: Plan, tile: int,
                   activation: str = "silu"):
    """``y`` [T, d] float32: for each token the weighted sum, over its
    pairs that land on a held expert, of that expert's gated feed-forward.

    x [T, d] in the compute dtype; weights [T, K] float32 (the router's,
    already renormalised); w_gate, w_up [held, d, f] and w_down [held, f, d]
    float32 masters, cast to x's dtype for the products; ``activation`` the
    gate's, a name of ``ACTIVATIONS``."""
    return _forward(x, weights, w_gate, w_up, w_down, plan, tile, activation)


def _routed_fwd(x, weights, w_gate, w_up, w_down, plan, tile, activation):
    y = _forward(x, weights, w_gate, w_up, w_down, plan, tile, activation)
    return y, (x, weights, w_gate, w_up, w_down, plan)


def _routed_bwd(tile, activation, res, dy):
    x, weights, w_gate, w_up, w_down, plan = res
    T, d = x.shape
    K = weights.shape[1]
    R = plan.row_token.shape[0]
    dtype = x.dtype
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), dtype)])
    dy_pad = jnp.concatenate([dy.astype(dtype), jnp.zeros((1, d), dtype)])
    row_weight = jnp.append(
        weights.astype(jnp.float32).reshape(T * K), 0.0
    )[plan.row_pair]
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))

    def step(w, row0, rows, carry, acc):
        wg_e, wu_e, wd_e = w
        dx_rows, dw_rows = carry
        a_gate, a_up, a_down = acc
        xt, dyt = _rows(plan, row0, rows, x_pad, dy_pad)
        wr = jax.lax.dynamic_slice(row_weight, (row0,), (rows,))
        g, u = _hidden(xt, wg_e, wu_e)
        s, slope = ACTIVATIONS[activation][1](g)
        h = (s * u).astype(dtype)
        o = _dot(h, wd_e, ((1,), (0,)))
        dw = jnp.sum(o * dyt.astype(jnp.float32), axis=-1)
        do = (dyt.astype(jnp.float32) * wr[:, None]).astype(dtype)
        dh = _dot(do, wd_e, ((1,), (1,)))
        du = (dh * s).astype(dtype)
        dg = (dh * u * slope()).astype(dtype)
        dxt = _dot(dg, wg_e, ((1,), (1,))) + _dot(du, wu_e, ((1,), (1,)))
        return (
            (
                jax.lax.dynamic_update_slice(
                    dx_rows, dxt.astype(dtype), (row0, 0)
                ),
                jax.lax.dynamic_update_slice(dw_rows, dw, (row0,)),
            ),
            (
                a_gate + _dot(xt, dg, ((0,), (0,))),
                a_up + _dot(xt, du, ((0,), (0,))),
                a_down + _dot(h, do, ((0,), (0,))),
            ),
        )

    (dx_rows, dw_rows), (d_gate, d_up, d_down) = _walk(
        plan, tile, (wg, wu, wd), step,
        (jnp.zeros((R + 1, d), dtype), jnp.zeros((R + 1,), jnp.float32)),
        tuple(jnp.zeros(w.shape[1:], jnp.float32)
              for w in (w_gate, w_up, w_down)),
    )
    dx = jnp.zeros((T, d), jnp.float32)
    for j in range(K):
        dx = dx + dx_rows[plan.pair_row[:, j]].astype(jnp.float32)
    dx = dx.astype(dtype)
    dweights = dw_rows[plan.pair_row].astype(weights.dtype)
    return (dx, dweights, d_gate.astype(w_gate.dtype),
            d_up.astype(w_up.dtype), d_down.astype(w_down.dtype), None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)

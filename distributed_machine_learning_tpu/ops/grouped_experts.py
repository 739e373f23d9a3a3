"""Dropless routed experts: order the token-expert pairs by expert, then a
grouped matrix product over the experts held here.

A top-k router sends every token to ``k`` of ``num_experts`` experts; this
chip holds ``held`` of them (ids ``first .. first + held - 1``).  The pairs
that land on a held expert are ordered by expert, each expert's run is
padded to whole tiles of ``tile`` rows, and a loop over the tiles *in use*
runs the gated feed-forward ``W_down(silu(W_gate x) * W_up x)`` of the
tile's expert on the tile's tokens.  No expert has a capacity and no pair
is dropped: the buffers are sized for the worst routing (every token's
``min(k, held)`` choices land here), the work is what the routing asks.
What the absent experts would add is left out.

Shapes are static and the trip count is not, so the loop is a ``while`` on
the device and its backward is written by hand (``jax.custom_vjp``): the
same loop again, recomputing a tile's activations and accumulating the
experts' weight gradients in float32.  Everything outside the loop is
cumulative sums and gathers, and one scatter of the pairs' indices into
their rows (``make_plan``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

DEFAULT_TILE = 256


class Plan(NamedTuple):
    """Where each pair's row lies, and what each row and tile holds."""

    row_token: jnp.ndarray    # [R] token of each padded row; T = the zero row
    row_pair: jnp.ndarray     # [R] flat pair (t * K + j); T * K = no pair
    tile_expert: jnp.ndarray  # [R // tile] held expert of each tile
    n_tiles: jnp.ndarray      # [] tiles in use
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
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    pstarts = ends - padded
    tile_expert = jnp.minimum(
        jnp.searchsorted(
            ends, jnp.arange(0, R, tile, dtype=jnp.int32), side="right"
        ),
        held - 1,
    ).astype(jnp.int32)
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
        tile_expert=tile_expert,
        n_tiles=ends[-1] // tile,
        pair_row=pair_row.reshape(T, K),
        sizes=sizes,
    )


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _tile_inputs(i, tile, plan: Plan, *row_arrays):
    """Tile ``i``'s expert and its rows of each of ``row_arrays``."""
    tok = jax.lax.dynamic_slice(plan.row_token, (i * tile,), (tile,))
    return plan.tile_expert[i], [a[tok] for a in row_arrays]


def _hidden(xt, wg_e, wu_e):
    g = _dot(xt, wg_e, ((1,), (0,)))
    u = _dot(xt, wu_e, ((1,), (0,)))
    return g, u


def _forward(x, weights, w_gate, w_up, w_down, plan: Plan, tile: int):
    T, d = x.shape
    R = plan.row_token.shape[0]
    dtype = x.dtype
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), dtype)])
    wg, wu, wd = (w.astype(dtype) for w in (w_gate, w_up, w_down))

    def body(i, out_rows):
        e, (xt,) = _tile_inputs(i, tile, plan, x_pad)
        g, u = _hidden(xt, wg[e], wu[e])
        h = (jax.nn.silu(g) * u).astype(dtype)
        o = _dot(h, wd[e], ((1,), (0,))).astype(dtype)
        return jax.lax.dynamic_update_slice(out_rows, o, (i * tile, 0))

    out_rows = jax.lax.fori_loop(
        0, plan.n_tiles, body, jnp.zeros((R + 1, d), dtype)
    )
    # One choice at a time: all K at once would be a [T, K, d] float32.
    weights = weights.astype(jnp.float32)
    y = jnp.zeros((T, d), jnp.float32)
    for j in range(weights.shape[1]):
        y = y + weights[:, j, None] * out_rows[plan.pair_row[:, j]].astype(
            jnp.float32
        )
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def routed_experts(x, weights, w_gate, w_up, w_down, plan: Plan, tile: int):
    """``y`` [T, d] float32: for each token the weighted sum, over its
    pairs that land on a held expert, of that expert's gated feed-forward.

    x [T, d] in the compute dtype; weights [T, K] float32 (the router's,
    already renormalised); w_gate, w_up [held, d, f] and w_down [held, f, d]
    float32 masters, cast to x's dtype for the products."""
    return _forward(x, weights, w_gate, w_up, w_down, plan, tile)


def _routed_fwd(x, weights, w_gate, w_up, w_down, plan, tile):
    y = _forward(x, weights, w_gate, w_up, w_down, plan, tile)
    return y, (x, weights, w_gate, w_up, w_down, plan)


def _routed_bwd(tile, res, dy):
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

    def add_at(acc, e, value):
        return jax.lax.dynamic_update_index_in_dim(acc, acc[e] + value, e, 0)

    def body(i, carry):
        dx_rows, dw_rows, d_gate, d_up, d_down = carry
        e, (xt, dyt) = _tile_inputs(i, tile, plan, x_pad, dy_pad)
        wr = jax.lax.dynamic_slice(row_weight, (i * tile,), (tile,))
        g, u = _hidden(xt, wg[e], wu[e])
        sg = jax.nn.sigmoid(g)
        s = g * sg
        h = (s * u).astype(dtype)
        o = _dot(h, wd[e], ((1,), (0,)))
        dw = jnp.sum(o * dyt.astype(jnp.float32), axis=-1)
        do = (dyt.astype(jnp.float32) * wr[:, None]).astype(dtype)
        dh = _dot(do, wd[e], ((1,), (1,)))
        du = (dh * s).astype(dtype)
        dg = (dh * u * (sg * (1.0 + g * (1.0 - sg)))).astype(dtype)
        dxt = _dot(dg, wg[e], ((1,), (1,))) + _dot(du, wu[e], ((1,), (1,)))
        return (
            jax.lax.dynamic_update_slice(
                dx_rows, dxt.astype(dtype), (i * tile, 0)
            ),
            jax.lax.dynamic_update_slice(dw_rows, dw, (i * tile,)),
            add_at(d_gate, e, _dot(xt, dg, ((0,), (0,)))),
            add_at(d_up, e, _dot(xt, du, ((0,), (0,)))),
            add_at(d_down, e, _dot(h, do, ((0,), (0,)))),
        )

    dx_rows, dw_rows, d_gate, d_up, d_down = jax.lax.fori_loop(
        0, plan.n_tiles, body,
        (
            jnp.zeros((R + 1, d), dtype),
            jnp.zeros((R + 1,), jnp.float32),
            jnp.zeros(w_gate.shape, jnp.float32),
            jnp.zeros(w_up.shape, jnp.float32),
            jnp.zeros(w_down.shape, jnp.float32),
        ),
    )
    dx = jnp.zeros((T, d), jnp.float32)
    for j in range(K):
        dx = dx + dx_rows[plan.pair_row[:, j]].astype(jnp.float32)
    dx = dx.astype(dtype)
    dweights = dw_rows[plan.pair_row].astype(weights.dtype)
    return (dx, dweights, d_gate.astype(w_gate.dtype),
            d_up.astype(w_up.dtype), d_down.astype(w_down.dtype), None)


routed_experts.defvjp(_routed_fwd, _routed_bwd)

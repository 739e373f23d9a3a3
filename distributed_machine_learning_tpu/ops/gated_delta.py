"""The gated delta rule, computed in chunks.

A linear-attention layer with a recurrent matrix state ``S`` (one
``[Dk, Dv]`` matrix a head) that decays and is corrected towards each new
value (Yang et al., "Gated Delta Networks", 2024).  Per position, in
float32::

    S     <- exp(g_t) * S
    delta  = beta_t * (v_t - S^T k_t)
    S     <- S + k_t delta^T
    o_t    = S^T q_t

``g_t <= 0`` is the log of the decay and ``beta_t`` in (0, 1) the write
strength.  A scan over positions would run 8,192 tiny steps a sequence;
the chunked form does the same sums with matrix products: inside a chunk
of ``C`` positions the deltas solve a unit-lower-triangular system built
from the decayed ``K K^T``, the outputs read the decayed ``Q K^T``, and
``S`` is carried from chunk to chunk.  The backward pass is autodiff's over
that program with the chunk step rematerialised, so it keeps one ``S`` a
chunk and never one a position.

The decay's cumulative sums, the solve and ``S`` are float32; the matrix
products take ``matmul_dtype`` operands (the model's compute dtype) and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_CHUNK = 64
# Heads worked at a time.  The backward pass keeps a head's chunk-local
# matrices ([S / C, C, C] and [S / C, C, Dk + Dv] float32, a dozen of them)
# until it has used them; a block of heads at a time, each rematerialised,
# holds that to the block's share.
HEAD_BLOCK = 8


def _mm(spec: str, a, b, dtype):
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                           matmul_dtype=jnp.float32):
    """``o`` [B, S, H, Dv] in float32 for q, k [B, S, H, Dk] (k of unit
    norm, q already scaled), v [B, S, H, Dv], g and beta [B, S, H], the
    state starting from nought.  Any S: a last partial chunk is padded
    with positions that write nothing.  Heads are independent and are
    worked ``HEAD_BLOCK`` at a time where that divides them."""
    H = q.shape[2]
    if not (HEAD_BLOCK < H and H % HEAD_BLOCK == 0):
        return _chunked(q, k, v, g, beta, chunk, matmul_dtype)
    n = H // HEAD_BLOCK

    def blocks(a):  # [B, S, H, ...] -> [n, B, S, head_block, ...]
        a = a.reshape(*a.shape[:2], n, HEAD_BLOCK, *a.shape[3:])
        return jnp.moveaxis(a, 2, 0)

    o = jax.lax.map(
        jax.checkpoint(
            lambda xs: _chunked(*xs, chunk, matmul_dtype)
        ),
        tuple(blocks(a) for a in (q, k, v, g, beta)),
    )                                                 # [n, B, S, hb, Dv]
    o = jnp.moveaxis(o, 0, 2)
    return o.reshape(*o.shape[:2], H, o.shape[-1])


def _chunked(q, k, v, g, beta, chunk: int, matmul_dtype):
    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    C = min(int(chunk), S)
    pad = -S % C
    if pad:
        # beta 0 writes nothing, g 0 decays nothing: the state passes
        # through the padding unchanged and its outputs are cut off.
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(a, widths + ((0, 0),)) for a in (q, k, v))
        g, beta = jnp.pad(g, widths), jnp.pad(beta, widths)
    N = (S + pad) // C

    def chunks(a):  # [B, S, H, ...] -> [N, B, H, C, ...]
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v = (chunks(a.astype(jnp.float32)) for a in (q, k, v))
    g, beta = chunks(g.astype(jnp.float32)), chunks(beta.astype(jnp.float32))

    gc = jnp.cumsum(g, axis=-1)                           # [N, B, H, C]
    rows = jnp.arange(C)
    lower = rows[:, None] >= rows[None, :]
    # exp of a masked difference, never a masked exp: above the diagonal
    # the difference is positive and its exp may overflow.
    decay = jnp.exp(
        jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf)
    )                                                     # [N, B, H, C, C]
    kb = k * beta[..., None]
    strict = rows[:, None] > rows[None, :]
    a_mat = jnp.where(
        strict, _mm("nbhik,nbhjk->nbhij", kb, k, matmul_dtype) * decay, 0.0
    )
    # (I + A) [U | W] = [V beta | K beta exp(gc)]: the deltas before the
    # incoming state is taken off (U) and what the incoming state
    # contributes to them (W), by forward substitution.
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1
    )
    solved = jax.lax.linalg.triangular_solve(
        a_mat + jnp.eye(C, dtype=jnp.float32), rhs,
        left_side=True, lower=True, unit_diagonal=True,
    )
    u, w = solved[..., :Dv], solved[..., Dv:]
    qk = _mm("nbhik,nbhjk->nbhij", q, k, matmul_dtype) * decay
    q_in = q * jnp.exp(gc)[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    carry_decay = jnp.exp(gc[..., -1])[..., None, None]   # [N, B, H, 1, 1]

    @jax.checkpoint
    def step(state, xs):
        u_n, w_n, qk_n, q_n, k_n, decay_n = xs
        v_new = u_n - _mm("bhck,bhkv->bhcv", w_n, state, matmul_dtype)
        o_n = (_mm("bhck,bhkv->bhcv", q_n, state, matmul_dtype)
               + _mm("bhij,bhjv->bhiv", qk_n, v_new, matmul_dtype))
        state = state * decay_n + _mm(
            "bhck,bhcv->bhkv", k_n, v_new, matmul_dtype
        )
        return state, o_n

    state0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
    _, o = jax.lax.scan(step, state0, (u, w, qk, q_in, k_out, carry_decay))
    # [N, B, H, C, Dv] -> [B, S, H, Dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(B, N * C, H, Dv)
    return o[:, :S]

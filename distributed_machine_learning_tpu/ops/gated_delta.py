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
``S`` is carried from chunk to chunk.  ``ops/pallas_gated_delta.py``
computes it in two Pallas kernels where the shapes fit them; ``_chunked``
is XLA's program for every other shape (its backward is autodiff's with
the chunk step rematerialised, so it keeps one ``S`` a chunk and never one
a position).

The decay's cumulative sums, the solve and ``S`` are float32; the matrix
products take ``matmul_dtype`` operands (the model's compute dtype) and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.ops import pallas_gated_delta

DEFAULT_CHUNK = 64


def _mm(spec: str, a, b, dtype):
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                           matmul_dtype=jnp.float32):
    """``o`` [B, S, Hv, Dv] in float32 for q, k [B, S, Hk, Dk] (k of unit
    norm, q already scaled), v [B, S, Hv, Dv], g and beta [B, S, Hv], the
    state starting from nought; each key head serves the ``Hv // Hk``
    value heads that follow it.  Any S: a last partial chunk is padded
    with positions that write nothing.

    Where the shapes fit its tiles (``pallas_gated_delta.fits``: chunk
    64, head sizes a multiple of 128) the Pallas kernels compute it, in
    the interpreter off a TPU; elsewhere ``_chunked``, XLA's program.  The
    registry counts each call as it is traced: ``gated_delta.kernel_calls``
    or ``gated_delta.xla_calls``."""
    from distributed_machine_learning_tpu import obs

    Hk, Dk = q.shape[2:]
    Hv, Dv = v.shape[2:]
    if pallas_gated_delta.fits(chunk, Dk, Dv, Hk, Hv):
        obs.get_registry().add("gated_delta.kernel_calls")
        return _kernel(q, k, v, g, beta, matmul_dtype)
    obs.get_registry().add("gated_delta.xla_calls")
    if Hv != Hk:
        q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    return _chunked(q, k, v, g, beta, chunk, matmul_dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kernel(q, k, v, g, beta, matmul_dtype):
    """The kernels' call: q, k and v as the projections' [B, S, heads*D],
    the chunk-local cumulative g and beta as [B, Hv, S / C, C] rows."""
    B, S, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    C = pallas_gated_delta.CHUNK
    pad = -S % C
    if pad:
        # beta 0 writes nothing, g 0 decays nothing (as in _chunked).
        widths = ((0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(a, widths + ((0, 0),)) for a in (q, k, v))
        g, beta = jnp.pad(g, widths), jnp.pad(beta, widths)
    N = (S + pad) // C

    def rows(a):  # [B, S, Hv] -> [B, Hv, N, C]
        return jnp.moveaxis(a.astype(jnp.float32).reshape(B, N, C, Hv), 3, 1)

    o = pallas_gated_delta.gated_delta_chunks(
        q.astype(jnp.float32).reshape(B, N * C, Hk * Dk),
        k.astype(jnp.float32).reshape(B, N * C, Hk * Dk),
        v.reshape(B, N * C, Hv * Dv),
        jnp.cumsum(rows(g), axis=-1),
        rows(beta),
        Dk,
        jnp.dtype(matmul_dtype),
        not _on_tpu(),
    )
    return o.reshape(B, N * C, Hv, Dv)[:, :S]


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

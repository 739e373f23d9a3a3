"""Pallas TPU kernels of the chunked gated delta rule.

``ops/gated_delta.py`` states the rule and its chunked form.  Here the
same sums run as two kernels under one ``jax.custom_vjp``:

* the forward walks grid ``(batch, key head, chunk block)``, the block
  axis in order.  A step reads ``CHUNKS`` chunks of 64 positions: q and k
  as one key head's ``[rows, Dk]`` column block of ``[B, S, Hk*Dk]`` and v
  as its value heads' ``[rows, G*Dv]`` block of ``[B, S, Hv*Dv]`` (the
  projections' own layout, no transpose; each key head serves the ``G =
  Hv / Hk`` value heads that follow it), the chunk-local cumulative ``g``
  and ``beta`` as ``[B, Hv, N, C]`` rows.  Each value head's ``[Dk, Dv]``
  float32 state stays in VMEM from step to step; a chunk's decay
  matrix, ``A``, the solve and the products never leave VMEM, and only
  ``o`` is written (float32), with, for the backward, the state at the
  start of every chunk block.
* the solve ``(I + A) [U | W] = [V beta | K beta exp(gc)]`` is float32:
  ``T = (I + A)^{-1}`` by forward substitution on the vector unit
  (``_inverse``), then ``T [V beta | ...]`` as float32 products.  A step
  forms every value head's ``T`` of a chunk before the chain of products
  that carries the state.  (At the cell's call the substitution was the
  fastest inverse measured; a product form loses every digit where keys
  are alike; PERF.md, section 6.)
* the backward walks the chunk blocks in reverse, carrying ``dS`` in VMEM.
  A step first runs its block forward from the saved state, keeping each
  chunk's state, ``T``, ``U`` and ``W`` in VMEM, then walks its chunks
  backward: ``dR = T^T dX``, ``dA = -strict(dR X^T)`` and the products'
  transposes give dq, dk, dv and the gradients of ``beta`` and of the
  cumulative ``g``; ``gated_delta.py`` sums the latter back to ``g``.

Every product takes ``matmul_dtype`` operands and accumulates in float32
where ``_chunked`` has one; the decays, ``beta``, the solve and the state
are float32.  Off a TPU the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
# Chunks a grid step walks: 8 (512 positions) where they divide the
# sequence's chunks.
CHUNKS = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def fits(chunk: int, key_head_dim: int, value_head_dim: int,
         key_heads: int, value_heads: int) -> bool:
    """Whether the kernels take a call: the chunk 64, both head sizes a
    multiple of 128 lanes, and whole groups of value heads a key head."""
    return (chunk == CHUNK and key_head_dim % 128 == 0
            and value_head_dim % 128 == 0 and value_heads % key_heads == 0)


def _dot(a, b, ca: int, cb: int, precision=None):
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _mm(a, b, ca: int, cb: int, dtype):
    """A product of ``dtype`` operands, accumulated in float32."""
    return _dot(a.astype(dtype), b.astype(dtype), ca, cb)


def _f32(a, b, ca: int, cb: int):
    """A float32 product (the solve's)."""
    return _dot(a, b, ca, cb, _HIGHEST)


def _grid(n: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return rows, cols


def _col(row):
    """[1, n] -> [n, 1], exactly (the diagonal of a broadcast)."""
    r, c = _grid(row.shape[-1])
    return jnp.sum(jnp.where(r == c, row, 0.0), axis=1, keepdims=True)


def _row(col):
    """[n, 1] -> [1, n], exactly."""
    r, c = _grid(col.shape[0])
    return jnp.sum(jnp.where(r == c, col, 0.0), axis=0, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _inverse(a):
    """``(I + a)^{-1}`` of a strictly lower-triangular float32 ``a`` by
    forward substitution, a column at a time: once row ``j`` of the
    inverse is final, every later row takes off ``a[i, j]`` times it."""
    n = a.shape[0]
    r, c = _grid(n)
    t = (r == c).astype(jnp.float32)
    for j in range(n - 1):
        lo = (j + 1) // 8 * 8  # the first row group that can change
        low = t[lo:] - a[lo:, j:j + 1] * t[j:j + 1]
        t = low if lo == 0 else jnp.concatenate([t[:lo], low], axis=0)
    return t


def _decays(gc_row):
    """The chunk's cumulative log decay as a column and its last value,
    and ``exp(gc_i - gc_j)`` below the diagonal (0 above it: the exp of a
    masked difference, never a masked exp, which could overflow)."""
    gc = _col(gc_row)
    r, c = _grid(gc.shape[0])
    decay = jnp.exp(jnp.where(r >= c, gc - gc_row, -jnp.inf))
    # The last value by a masked sum, so that it broadcasts both ways.
    last = _rowsum(jnp.where(c[:1] == gc.shape[0] - 1, gc_row, 0.0))
    return gc, last, decay


class _System(NamedTuple):
    """One value head's chunk: gc and its last value (columns), the
    decays, beta as a column, K beta and ``A`` strictly below the
    diagonal."""
    gc: jax.Array
    last: jax.Array
    decay: jax.Array
    beta: jax.Array
    kb: jax.Array
    a: jax.Array


def _system(k, gc_row, beta_row, dtype) -> _System:
    gc, last, decay = _decays(gc_row)
    beta = _col(beta_row)
    r, c = _grid(k.shape[0])
    kb = k * beta
    a = jnp.where(r > c, _mm(kb, k, 1, 1, dtype) * decay, 0.0)
    return _System(gc, last, decay, beta, kb, a)


def _solved(t, v, system: _System):
    """``[U | W] = T [V beta | K beta exp(gc)]``, float32."""
    return (_f32(t, v * system.beta, 1, 0),
            _f32(t, system.kb * jnp.exp(system.gc), 1, 0))


def _chunk_forward(q, k, v, system: _System, t, state, dtype):
    """One chunk of one value head, given its system and ``T``: (o [C,
    Dv], the state after it)."""
    gc, last, decay = system.gc, system.last, system.decay
    u, w = _solved(t, v, system)
    v_new = u - _mm(w, state, 1, 0, dtype)
    qk = _mm(q, k, 1, 1, dtype) * decay
    o = (_mm(q * jnp.exp(gc), state, 1, 0, dtype)
         + _mm(qk, v_new, 1, 0, dtype))
    state = state * jnp.exp(last) + _mm(
        k * jnp.exp(last - gc), v_new, 0, 0, dtype
    )
    return o, state


def _chunk_backward(q, k, v, gc_row, beta_row, state, t, u, w, do, ds,
                    dtype):
    """One chunk of one value head, backward: from ``do`` and ``ds`` (the
    gradient of the state after the chunk), given the state before it and
    the forward's ``T``, ``U``, ``W``: (dq, dk, dv, d gc as a row, d beta
    as a row, the gradient of the state before the chunk)."""
    n = k.shape[0]
    r, c = _grid(n)
    gc, last, decay = _decays(gc_row)
    beta = _col(beta_row)
    e, f, e_last = jnp.exp(gc), jnp.exp(last - gc), jnp.exp(last)
    kb = k * beta
    kk = _mm(kb, k, 1, 1, dtype)
    qk_raw = _mm(q, k, 1, 1, dtype)
    q_in, k_out = q * e, k * f
    v_new = u - _mm(w, state, 1, 0, dtype)

    # o = q_in S + (qk_raw * decay) v_new;  S' = S e_last + k_out^T v_new
    dv_new = (_mm(qk_raw * decay, do, 0, 0, dtype)
              + _mm(k_out, ds, 1, 0, dtype))
    d_qk = _mm(do, v_new, 1, 1, dtype)
    d_qin = _mm(do, state, 1, 1, dtype)
    d_kout = _mm(v_new, ds, 1, 1, dtype)
    ds_before = (ds * e_last + _mm(q_in, do, 0, 0, dtype)
                 - _mm(w, dv_new, 0, 0, dtype))
    d_elast = jnp.sum(_rowsum(state * ds), axis=0, keepdims=True)
    # v_new = U - W S;  [U | W] = T R:  dR = T^T dX, dA = -strict(dR X^T)
    dw = -_mm(dv_new, state, 1, 1, dtype)
    dr_u = _f32(t, dv_new, 0, 0)
    dr_w = _f32(t, dw, 0, 0)
    da = jnp.where(r > c, -(_f32(dr_u, u, 1, 1) + _f32(dr_w, w, 1, 1)), 0.0)
    # R = [v beta | kb e],  A = strict(kk * decay)
    dv = dr_u * beta
    d_beta = _rowsum(dr_u * v)
    d_kb = dr_w * e + _mm(da * decay, k, 1, 0, dtype)
    d_e = _rowsum(dr_w * kb)
    d_decay = da * kk + d_qk * qk_raw
    d_qkraw = d_qk * decay
    dq = _mm(d_qkraw, k, 1, 0, dtype) + d_qin * e
    dk = (_mm(da * decay, kb, 0, 0, dtype) + _mm(d_qkraw, q, 0, 0, dtype)
          + d_kout * f + d_kb * beta)
    d_beta = d_beta + _rowsum(d_kb * k)
    d_e = d_e + _rowsum(d_qin * q)
    d_f = _rowsum(d_kout * k)
    # decay_ij = exp(gc_i - gc_j), e = exp(gc), f = exp(gc_last - gc)
    p = d_decay * decay
    d_gc = _row(d_e * e - d_f * f + _rowsum(p)) - jnp.sum(
        p, axis=0, keepdims=True
    )
    d_last = d_elast * e_last + jnp.sum(d_f * f, axis=0, keepdims=True)
    d_gc = d_gc + jnp.where(c[:1] == n - 1, d_last, 0.0)
    return dq, dk, dv, d_gc, _row(d_beta), ds_before


def _forward_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, o_ref, *rest,
                    chunks: int, heads: int, dtype):
    states_ref = rest[0] if len(rest) == 2 else None
    state = rest[-1]
    n = pl.program_id(2)
    dv = v_ref.shape[-1] // heads

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if states_ref is not None:
        states_ref[0, :, 0] = state[...]

    def step(c, carry):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        at = pl.ds(n * chunks + c, 1)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        vs = [v_ref[0, rows, j * dv:(j + 1) * dv].astype(jnp.float32)
              for j in range(heads)]
        systems = [_system(k, gc_ref[0, j, at, :], beta_ref[0, j, at, :],
                           dtype) for j in range(heads)]
        # Every head's solve ahead of the state's chain of products: 16 %
        # off the forward on the chip (PERF.md, section 6).
        ts = [_inverse(system.a) for system in systems]
        for j in range(heads):
            o, state[j] = _chunk_forward(q, k, vs[j], systems[j], ts[j],
                                         state[j], dtype)
            o_ref[0, rows, j * dv:(j + 1) * dv] = o
        return carry

    jax.lax.fori_loop(0, chunks, step, 0)


def _backward_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, s0_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dgc_ref, dbeta_ref,
                     ds, run, s_keep, t_keep, u_keep, w_keep,
                     *, chunks: int, heads: int, dtype):
    i = pl.program_id(2)
    block = pl.num_programs(2) - 1 - i
    dv = v_ref.shape[-1] // heads

    @pl.when(i == 0)
    def _():
        ds[...] = jnp.zeros_like(ds)

    run[...] = s0_ref[0, :, 0]

    def forward(c, carry):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        at = pl.ds(block * chunks + c, 1)
        k = k_ref[0, rows, :]
        vs = [v_ref[0, rows, j * dv:(j + 1) * dv].astype(jnp.float32)
              for j in range(heads)]
        systems = [_system(k, gc_ref[0, j, at, :], beta_ref[0, j, at, :],
                           dtype) for j in range(heads)]
        ts = [_inverse(system.a) for system in systems]
        for j in range(heads):
            gc, last = systems[j].gc, systems[j].last
            u, w = _solved(ts[j], vs[j], systems[j])
            s = run[j]
            s_keep[c, j], t_keep[c, j], u_keep[c, j], w_keep[c, j] = (
                s, ts[j], u, w)
            v_new = u - _mm(w, s, 1, 0, dtype)
            run[j] = s * jnp.exp(last) + _mm(
                k * jnp.exp(last - gc), v_new, 0, 0, dtype
            )
        return carry

    jax.lax.fori_loop(0, chunks, forward, 0)

    def backward(ci, carry):
        c = chunks - 1 - ci
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        at = pl.ds(block * chunks + c, 1)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        dq_sum = dk_sum = None
        for j in range(heads):
            lanes = slice(j * dv, (j + 1) * dv)
            dq, dk, dvj, dgc, dbeta, ds[j] = _chunk_backward(
                q, k, v_ref[0, rows, lanes].astype(jnp.float32),
                gc_ref[0, j, at, :], beta_ref[0, j, at, :], s_keep[c, j],
                t_keep[c, j], u_keep[c, j], w_keep[c, j],
                do_ref[0, rows, lanes], ds[j], dtype,
            )
            dv_ref[0, rows, lanes] = dvj.astype(dv_ref.dtype)
            dgc_ref[0, j, at, :] = dgc
            dbeta_ref[0, j, at, :] = dbeta
            dq_sum = dq if dq_sum is None else dq_sum + dq
            dk_sum = dk if dk_sum is None else dk_sum + dk
        dq_ref[0, rows, :] = dq_sum
        dk_ref[0, rows, :] = dk_sum
        return carry

    jax.lax.fori_loop(0, chunks, backward, 0)


def _chunks_a_step(n: int) -> int:
    return next(c for c in (CHUNKS, 4, 2, 1) if n % c == 0)


def _layout(q, v, gc, dk: int):
    """(B, N, Hk, G, Dk, Dv, chunks a step, grid)."""
    B, _, key_width = q.shape
    _, Hv, N, _ = gc.shape
    Hk = key_width // dk
    G = Hv // Hk
    Dv = v.shape[-1] // Hv
    chunks = _chunks_a_step(N)
    return B, N, Hk, G, dk, Dv, chunks, (B, Hk, N // chunks)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _forward(q, k, v, gc, beta, dk: int, dtype, interpret: bool,
             with_states: bool):
    B, N, Hk, G, Dk, Dv, chunks, grid = _layout(q, v, gc, dk)
    L = chunks * CHUNK
    per_head = pl.BlockSpec((1, G, N, CHUNK), lambda b, h, n: (b, h, 0, 0))
    out_specs = [pl.BlockSpec((1, L, G * Dv), lambda b, h, n: (b, n, h))]
    out_shape = [jax.ShapeDtypeStruct((B, N * CHUNK, G * Hk * Dv),
                                      jnp.float32)]
    if with_states:
        out_specs.append(pl.BlockSpec((1, G, 1, Dk, Dv),
                                      lambda b, h, n: (b, h, n, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (B, G * Hk, N // chunks, Dk, Dv), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_forward_kernel, chunks=chunks, heads=G,
                          dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, Dk), lambda b, h, n: (b, n, h)),
            pl.BlockSpec((1, L, Dk), lambda b, h, n: (b, n, h)),
            pl.BlockSpec((1, L, G * Dv), lambda b, h, n: (b, n, h)),
            per_head,
            per_head,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, Dk, Dv), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_fwd",
    )(q, k, v, gc, beta)
    return tuple(out) if with_states else out[0]


def _backward(q, k, v, gc, beta, states, do, dk: int, dtype,
              interpret: bool):
    B, N, Hk, G, Dk, Dv, chunks, grid = _layout(q, v, gc, dk)
    L = chunks * CHUNK
    nb = N // chunks

    def rows(width):
        return pl.BlockSpec((1, L, width),
                            lambda b, h, i: (b, nb - 1 - i, h))

    per_head = pl.BlockSpec((1, G, N, CHUNK), lambda b, h, i: (b, h, 0, 0))
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_backward_kernel, chunks=chunks, heads=G,
                          dtype=dtype),
        grid=grid,
        in_specs=[
            rows(Dk), rows(Dk), rows(G * Dv), per_head, per_head,
            pl.BlockSpec((1, G, 1, Dk, Dv),
                         lambda b, h, i: (b, h, nb - 1 - i, 0, 0)),
            rows(G * Dv),
        ],
        out_specs=[rows(Dk), rows(Dk), rows(G * Dv), per_head, per_head],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, f32),
            jax.ShapeDtypeStruct(k.shape, f32),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(gc.shape, f32),
            jax.ShapeDtypeStruct(beta.shape, f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, Dk, Dv), f32),                  # dS
            pltpu.VMEM((G, Dk, Dv), f32),                  # the state run
            pltpu.VMEM((chunks, G, Dk, Dv), f32),          # each chunk's S
            pltpu.VMEM((chunks, G, CHUNK, CHUNK), f32),    # T
            pltpu.VMEM((chunks, G, CHUNK, Dv), f32),       # U
            pltpu.VMEM((chunks, G, CHUNK, Dk), f32),       # W
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_bwd",
    )(q, k, v, gc, beta, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gated_delta_chunks(q, k, v, gc, beta, dk, dtype, interpret):
    """o ``[B, S, Hv*Dv]`` float32 from q, k ``[B, S, Hk*Dk]`` float32, v
    ``[B, S, Hv*Dv]``, and the chunk-local cumulative g and beta
    ``[B, Hv, S / 64, 64]`` float32; S a multiple of 64."""
    return _forward(q, k, v, gc, beta, dk, dtype, interpret, False)


def _vjp_forward(q, k, v, gc, beta, dk, dtype, interpret):
    o, states = _forward(q, k, v, gc, beta, dk, dtype, interpret, True)
    return o, (q, k, v, gc, beta, states)


def _vjp_backward(dk, dtype, interpret, res, do):
    q, k, v, gc, beta, states = res
    return tuple(_backward(q, k, v, gc, beta, states, do, dk, dtype,
                           interpret))


gated_delta_chunks.defvjp(_vjp_forward, _vjp_backward)

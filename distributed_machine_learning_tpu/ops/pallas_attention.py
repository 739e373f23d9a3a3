"""Pallas TPU flash-attention kernel.

The hot op of the transformer family (SURVEY.md §3.3: the reference's inner
loop is ``nn.MultiheadAttention`` at `ray-tune-hpo-regression.py:139`, lowered
to cuDNN on its CUDA stack). Here the softmax-attention forward is a hand-
written Pallas kernel tiled for the MXU:

* grid ``(batch*heads, q_blocks, kv_blocks)`` with the kv dimension innermost,
  so each (q-block, head) streams key/value blocks HBM -> VMEM while running
  (max, denom, accumulator) statistics live in VMEM scratch — the flash
  online-softmax recurrence; peak VMEM is O(block_q * (head_dim + block_k))
  instead of O(seq^2).
* both matmuls (`q k^T` and `p v`) hit the MXU via ``jnp.dot`` with
  ``preferred_element_type=float32``; the softmax chain stays on the VPU in
  float32 regardless of input dtype (bfloat16 inputs supported).
* causal masking skips fully-masked kv blocks entirely (``@pl.when``), so the
  causal forward does ~half the work.

Gradients: ``jax.custom_vjp`` with hand-written Pallas backward kernels —
the forward additionally emits per-row logsumexp; the backward recomputes
``P = exp(logits - lse)`` per block (flash-style) in two passes, a dK/dV
kernel (kv block resident, q blocks streaming) and a dQ kernel (q block
resident, kv blocks streaming), with the standard ``delta = rowsum(dO*O)``
correction. Exact gradients, O(block) memory, every matmul on the MXU.

Selected via ``MultiHeadAttention(attention_type="flash")`` (models/layers.py),
which routes to this kernel on TPU backends and to the differentiable
``blockwise_attention`` scan elsewhere (compiled Mosaic kernels only exist for
TPU). Off-TPU the kernel itself still runs under Pallas interpret mode — the
tests exercise exactly that.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
):
    """One (bh, q_block, kv_block) grid step of the online-softmax recurrence."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: a kv block strictly above the diagonal of this q block is all
    # masked; skip its matmuls entirely.
    q_start = q_idx * block_q
    k_start = kv_idx * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)  # [block_k, d]

        logits = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [block_q, block_k]

        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + k_start
            logits = jnp.where(rows >= cols, logits, NEG_INF)

        m_prev = m_ref[:, :1]  # [block_q, 1]
        l_prev = l_ref[:, :1]
        row_max = jnp.max(logits, axis=-1, keepdims=True)  # [block_q, 1]
        m_new = jnp.maximum(m_prev, row_max)
        # Fully-masked rows keep m=-inf; exp against a safe max stays 0.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe)
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Live iff this kv block intersects the causal triangle of this q block.
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            _compute()

    else:
        _compute()

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # Logsumexp per row, for the backward kernels' softmax recompute
        # (P = exp(logits - lse)). Fully-masked rows keep -inf.
        m = m_ref[:, :1]
        lse = jnp.where(jnp.isfinite(m), m + jnp.log(denom), NEG_INF)
        lse_ref[0, 0] = lse[:, 0]


def _tileable_block(S: int, target: int, align: int) -> Optional[int]:
    """Largest block <= ``target`` the TPU tiling accepts along an axis of
    length S: the whole axis, or a divisor of S that is a multiple of
    ``align``. None where S admits neither."""
    if S <= target:
        return S
    b = (target // align) * align
    while b >= align:
        if S % b == 0:
            return b
        b -= align
    return None


def _tileable_blocks(S: int, block_q: int, block_k: int):
    """(q block, kv block) for the compiled kernels, None where S admits
    none: ``block_q`` is the LAST dim of the lse/delta blocks
    ``(1, 1, block_q)`` so it must be a multiple of 128; ``block_k`` is only
    ever second to last (``(1, block_k, D)``) so a multiple of 8 does."""
    return _tileable_block(S, block_q, 128), _tileable_block(S, block_k, 8)


def _adjust_blocks(S: int, block_q: int, block_k: int, interpret: bool):
    """Fit the requested blocks to S.

    Compiled (Mosaic) kernels only take blocks the chip's tiling accepts
    (``_tileable_blocks``), or blocks spanning the whole axis. An S that
    admits no such block under the caller's cap raises — nothing is padded
    and nothing gives way to another kernel. The interpreter has no
    tiling, so there any divisor goes."""
    if interpret:
        from distributed_machine_learning_tpu.ops.attention import (
            largest_divisor_block,
        )

        return (
            largest_divisor_block(S, block_q),
            largest_divisor_block(S, block_k),
        )
    bq, bk = _tileable_blocks(S, block_q, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention cannot tile seq len {S} for the TPU: it needs "
            f"a q block <= {block_q} that divides {S} and is a multiple of "
            f"128 (got {bq}) and a kv block <= {block_k} that divides {S} "
            f"and is a multiple of 8 (got {bk}), or blocks spanning the "
            f"whole axis; use a sequence length with such a divisor or "
            f"another attention_type"
        )
    return bq, bk


def _to_bh(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _kv_row_map(H: int, Hkv: int):
    """Grid-row -> kv-tensor row for grouped-query attention.

    The q side enumerates rows ``bh = b*H + h``; with ``Hkv`` kv heads the
    matching kv row is ``b*Hkv + h // group`` (``group = H // Hkv``) — k/v
    stay at kv_heads in HBM/VMEM and are STREAMED once per q head instead of
    being ``jnp.repeat``-ed into a full-H tensor first (VERDICT r3 next #4:
    the repeat materialization is pure HBM traffic + memory, which is most
    of GQA's cost at long context)."""
    if Hkv == H:
        return lambda bh: bh
    group = H // Hkv
    return lambda bh: (bh // H) * Hkv + (bh % H) // group


def _flash_forward(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    *,
    with_lse: bool = False,
):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if k.shape != v.shape or k.shape[0] != B or k.shape[1] != S \
            or k.shape[3] != D:
        raise ValueError(
            f"k/v shapes {k.shape}/{v.shape} incompatible with q {q.shape}"
        )
    if H % Hkv != 0:
        raise ValueError(
            f"num_heads {H} must be a multiple of kv heads {Hkv}"
        )
    block_q, block_k = _adjust_blocks(S, block_q, block_k, interpret)
    nq, nk = S // block_q, S // block_k

    # [B, S, H, D] -> [B*H, S, D]: one grid row per (batch, head).
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    kv_row = _kv_row_map(H, Hkv)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
    )

    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),  # running max
        pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
        pltpu.VMEM((block_q, D), jnp.float32),  # output accumulator
    ]

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            # lse rides as [B*H, 1, S] so its block (1, 1, block_q) keeps the
            # lane dim 128-aligned (Mosaic tiling rules reject (1, block_q)
            # blocks over a [B*H, S] array: the sublane dim 1 neither
            # divides by 8 nor equals B*H).
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        scratch_shapes=scratch_shapes,
        interpret=interpret,
    )(qb, kb, vb)

    out = _from_bh(out, B, H)
    return (out, lse) if with_lse else out


def _bwd_recompute(q, k, v, do, lse, delta, q_start, k_start, scale, causal):
    """Shared backward block math: recompute P from the forward's logsumexp
    and form dS — used identically by both backward kernels.

    Returns (p, ds): p = exp(logits - lse) [bq, bk] with masked/fully-masked
    rows zeroed; ds = p * (dO V^T - delta) * scale."""
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                      # [bq, bk]
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + k_start
        logits = jnp.where(rows >= cols, logits, NEG_INF)
    p = jnp.where(jnp.isfinite(lse), jnp.exp(logits - lse), 0.0)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # [bq, bk]
    ds = p * (dp - delta) * scale
    return p, ds


def _bwd_dkdv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, causal: bool, nq: int,
):
    """dK/dV for one kv block: grid (b*kv_head, kv_block, q_stream).

    Streams q/do/lse/delta blocks past a resident kv block, recomputing
    P = exp(logits - lse) from the forward's logsumexp, accumulating
    dV += P^T dO and dK += dS^T Q in VMEM scratch.

    Under grouped-query attention the innermost axis streams ``nq`` q
    blocks for EACH of the group's q heads (length nq*group): the grouped
    dK/dV reduction happens in the accumulator, so gradients never
    materialize at full num_heads."""
    pid = pl.program_id(2)
    q_idx = pid % nq  # q block within the current group head's stream
    kv_idx = pl.program_id(1)
    num_q = pl.num_programs(2)

    @pl.when(pid == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = q_idx * block_q
    k_start = kv_idx * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        do = do_ref[0].astype(jnp.float32)        # [bq, d]
        lse = lse_ref[0, 0][:, None]              # [bq, 1]
        delta = delta_ref[0, 0][:, None]          # [bq, 1]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]

        p, ds = _bwd_recompute(
            q, k, v, do, lse, delta, q_start, k_start, scale, causal
        )
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [bk, d]
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [bk, d]

    if causal:
        # Live iff some row of this q block can attend into this kv block.
        @pl.when(q_start + block_q - 1 >= k_start)
        def _():
            _compute()

    else:
        _compute()

    @pl.when(pid == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_acc,
    *, scale: float, block_q: int, block_k: int, causal: bool,
):
    """dQ for one q block: grid (bh, q_block, kv_block), kv innermost."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = q_idx * block_q
    k_start = kv_idx * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)

        _, ds = _bwd_recompute(
            q, k, v, do, lse, delta, q_start, k_start, scale, causal
        )
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            _compute()

    else:
        _compute()

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, do, scale, causal, block_q, block_k, interpret,
    *, q_side=None,
):
    """Flash backward via two Pallas kernels (dK/dV, then dQ).

    delta = rowsum(dO * O) is the standard precomputed correction; the
    kernels recompute P from the forward's logsumexp, so backward memory is
    O(block) like the forward — no S x S materialization.

    ``q_side``: optional precomputed ``(qb, dob, delta)`` in [B*H, ...]
    layout — callers that invoke this per k/v chunk with the SAME q side
    (the flash ring's backward scan) hoist the loop-invariant transposes
    and the delta reduction out of their loop.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    block_q, block_k = _adjust_blocks(S, block_q, block_k, interpret)
    nq, nk = S // block_q, S // block_k
    kv_row = _kv_row_map(H, Hkv)

    kb, vb = _to_bh(k), _to_bh(v)
    if q_side is None:
        qb, dob = _to_bh(q), _to_bh(do)
        ob = _to_bh(out)
        delta = jnp.sum(
            dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1
        )[:, None, :]  # [B*H, 1, S], same layout as lse
    else:
        qb, dob, delta = q_side

    # dkdv grid: (b*kv_head, kv, q-stream) — the innermost axis streams the
    # nq q blocks of EACH of the group's q heads past the resident kv block
    # (length nq*group), so grouped dK/dV accumulate in scratch and the
    # outputs stay at kv_heads rows.
    def _q_row(r, j):
        # r = b*Hkv + kv_head; j = head_in_group*nq + q_block.
        return (r // Hkv) * H + (r % Hkv) * group + j // nq

    dkdv = pl.pallas_call(
        functools.partial(
            _bwd_dkdv_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal, nq=nq,
        ),
        grid=(B * Hkv, nk, nq * group),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda r, ki, j: (_q_row(r, j), j % nq, 0)),
            pl.BlockSpec((1, block_q, D),
                         lambda r, ki, j: (_q_row(r, j), j % nq, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda r, ki, j: (_q_row(r, j), 0, j % nq)),
            pl.BlockSpec((1, 1, block_q),
                         lambda r, ki, j: (_q_row(r, j), 0, j % nq)),
            pl.BlockSpec((1, block_k, D), lambda r, ki, j: (r, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda r, ki, j: (r, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda r, ki, j: (r, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda r, ki, j: (r, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )
    dk, dv = dkdv(qb, dob, lse, delta, kb, vb)

    q_spec = pl.BlockSpec((1, block_q, D), lambda bh, a, b: (bh, a, 0))
    q_vec = pl.BlockSpec((1, 1, block_q), lambda bh, a, b: (bh, 0, a))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal,
        ),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (kv_row(bh), ki, 0)),
            q_spec,
            q_spec,
            q_vec,
            q_vec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(kb, vb, qb, dob, lse, delta)

    return (
        _from_bh(dq, B, H), _from_bh(dk, B, Hkv), _from_bh(dv, B, Hkv)
    )


def _default_blocks(S: int, D: int, block_q, block_k, backward: bool = False):
    """Resolve block sizes: as large as VMEM comfortably allows.

    Recorded on a v5e chip in an earlier round (not measured on today's
    code): 128x128 blocks ran 54ms forward vs XLA's fused attention at
    24ms (seq 4096, D=64) — grid overhead and tiny MXU matmuls dominated;
    1024-tile forwards ~20% faster than XLA, and with the 512-tile
    backward the fwd+bwd pair 2.0x faster. The caps clamp by head dim to keep the
    per-step VMEM working set (f32 [bq, bk] intermediates + streamed
    blocks + Pallas double-buffering) inside the ~16MB scoped budget:
    1024-tile forwards fail Mosaic compilation at D=256 (measured), and
    1024-tile backwards fail inside real models even at D=64 (stack
    measured 16.69MB vs the 16MB limit).
    """
    if backward:
        # The backward cap binds EXPLICIT blocks too (the pre-kernel
        # backward enforced a hard 512 ceiling the same way): a user-tuned
        # forward tile must not push the backward's larger working set past
        # VMEM. 512 max: the dK/dV kernel holds FOUR [bq, bk] f32
        # intermediates (logits, p, dp, ds), and at 1024 tiles Mosaic's
        # scoped-vmem stack measured 16.69MB against the 16MB limit inside
        # a real model's backward (OOM observed on v5e at D=64, seq 2048 —
        # the standalone microbench sat just under the line).
        cap = 512 if D <= 256 else 256
        bq = min(cap, S) if block_q is None else min(block_q, cap, S)
        bk = min(cap, S) if block_k is None else min(block_k, cap, S)
        return bq, bk
    # The cap binds EXPLICIT blocks too (same policy as the backward):
    # 1024-tile forwards fail Mosaic compilation at D=256 (measured), so a
    # user-pinned block_q=1024 there would be a compile error, not a knob.
    cap = 1024 if D <= 128 else (512 if D <= 512 else 256)
    bq = min(cap, S) if block_q is None else min(block_q, cap, S)
    bk = min(cap, S) if block_k is None else min(block_k, cap, S)
    return bq, bk


def flash_can_tile(S: int, D: int) -> bool:
    """Whether the compiled forward AND backward kernels can tile seq len S
    at their default blocks — what the automatic routes (softmax->flash,
    ring, Ulysses) ask before selecting the kernel."""
    return all(
        None not in _tileable_blocks(
            S, *_default_blocks(S, D, None, None, backward=backward)
        )
        for backward in (False, True)
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: Optional[float] = None,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash softmax attention. q: [B, S, H, D] -> [B, S, H, D].

    k, v: [B, S, Hkv, D] with ``H % Hkv == 0`` — grouped-query attention is
    native: kv tensors stay at Hkv heads end to end (HBM, VMEM streaming,
    and the dK/dV gradients), no ``jnp.repeat`` materialization anywhere.
    ``scale`` defaults to 1/sqrt(D) (override = the reference's intended
    ``key_dim_scaling`` knob, SURVEY.md §2 C19). Block sizes default to the
    measured-fastest large tiles (``_default_blocks``). ``interpret=True``
    runs the kernel in the Pallas interpreter (CPU tests); on TPU leave it
    False.
    """
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    bq, bk = _default_blocks(q.shape[1], q.shape[-1], block_q, block_k)
    return _flash_forward(q, k, v, s, causal, bq, bk, interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    S, D = q.shape[1], q.shape[-1]
    bq, bk = _default_blocks(S, D, block_q, block_k)
    # An S the backward cannot tile is refused here, while the forward is
    # traced, not halfway into the gradient's trace.
    _adjust_blocks(
        S, *_default_blocks(S, D, block_q, block_k, backward=True), interpret
    )
    out, lse = _flash_forward(
        q, k, v, s, causal, bq, bk, interpret, with_lse=True
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    # Hand-written Pallas backward (dK/dV kernel + dQ kernel), recomputing
    # P from the forward's saved logsumexp — O(block) memory like the
    # forward, all four matmuls per block on the MXU.
    q, k, v, out, lse = res
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    bq, bk = _default_blocks(
        q.shape[1], q.shape[-1], block_q, block_k, backward=True
    )
    return _flash_backward(
        q, k, v, out, lse, g, s, causal, bq, bk, interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)

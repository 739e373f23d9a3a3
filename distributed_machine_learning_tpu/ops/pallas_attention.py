"""Pallas TPU flash-attention kernel.

The hot op of the transformer family (SURVEY.md §3.3: the reference's inner
loop is ``nn.MultiheadAttention`` at `ray-tune-hpo-regression.py:139`, lowered
to cuDNN on its CUDA stack). Here the softmax-attention forward is a hand-
written Pallas kernel tiled for the MXU:

* grid ``(batch*heads, q_blocks, kv_blocks)`` with the kv dimension innermost,
  so each (q-block, head) streams key/value blocks HBM -> VMEM while running
  (max, denom, accumulator) statistics live in VMEM scratch — the flash
  online-softmax recurrence; peak VMEM is O(block_q * (head_dim + block_k))
  instead of O(seq^2). Where one kv block spans the sequence (S <= 2048 at
  the default blocks) the recurrence has one step, and the kernel is a plain
  softmax over the block with no running state.
* every matmul is a ``dot_general`` with ``preferred_element_type=float32``,
  and the softmax chain, the row statistics, ``lse``, ``delta`` and every
  accumulator are float32 whatever the input. bfloat16 inputs without a
  mask reach the MXU as they are, with ``p`` and ``ds`` narrowed to bfloat16
  at the products that consume them; any other input has float32 operands,
  which Mosaic feeds to the MXU in one bf16 pass of its own, so the two
  give the same bits (``_operand_dtype`` has the readings).
* the work per score is what the body can see it needs. Without ``causal``
  nothing can make a score infinite, so there is no mask and no guard (a
  non-finite input then reaches the output as NaN). In the forward a
  ``scale`` that is a power of two (head size 64: 0.125) is multiplied into
  the ``[block_q, D]`` q block, exactly, and not into the ``[block_q,
  block_k]`` scores; the backward kernels keep it on their tiles, where
  moving it measured nothing.
* causal masking skips fully-masked kv blocks entirely (``@pl.when``), so the
  causal forward does ~half the work, and keeps its guards: a row with
  nothing to attend gives ``lse = -inf`` and zeros, which the ring's merge
  of chunk results relies on.

Gradients: ``jax.custom_vjp`` with hand-written Pallas backward kernels —
the forward additionally emits per-row logsumexp; the backward recomputes
``P = exp(logits - lse)`` per block (flash-style) in two passes, a dK/dV
kernel (kv block resident, q blocks streaming) and a dQ kernel (q block
resident, kv blocks streaming), with the standard ``delta = rowsum(dO*O)``
correction. The dK/dV kernel works on transposed scores (``k q^T``), so that
``P^T dO`` and ``dS^T Q`` are plain products and ``lse``/``delta`` broadcast
along lanes as they are stored. Exact gradients, O(block) memory, every
matmul on the MXU.

Selected via ``MultiHeadAttention(attention_type="flash")`` (models/layers.py),
which routes to this kernel on TPU backends and to the differentiable
``blockwise_attention`` scan elsewhere (compiled Mosaic kernels only exist for
TPU). Off-TPU the kernel itself still runs under Pallas interpret mode — the
tests exercise exactly that.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _operand_dtype(dtype, causal: bool):
    """What the matmuls take (accumulation is float32 either way).

    bfloat16 inputs without a mask go in as they are, and ``p`` and ``ds``
    are narrowed beside them. Float32 operands reach the MXU in one bf16
    pass of Mosaic's own, so the bits are the same and so is the time
    (v5e, [32, 2048, 8, 64]: forward 3.777 against 3.797 ms, dK/dV 6.054
    against 6.053); what the narrow operands buy is VMEM, 11.9 MiB in place
    of 15.5 of the 16 at the backward's 1024 tiles. Under a mask the
    explicit narrowing measured slower (dK/dV 6.59 against 5.99 ms, dQ 4.99
    against 4.71), so there, as for any other input, operands are float32."""
    narrow = dtype == jnp.bfloat16 and not causal
    return jnp.bfloat16 if narrow else jnp.float32


def _folds_scale(scale: float) -> bool:
    """A power of two multiplies into q exactly, so it can leave the
    [block_q, block_k] tile without changing a bit; any other scale stays
    on the float32 scores."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _causal_keep(shape, q_start, k_start, q_axis: int):
    """Where a [.., ..] tile of scores may attend: q position >= k position,
    with the q positions along ``q_axis``."""
    q_pos = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) + q_start
    k_pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) + k_start
    return q_pos >= k_pos


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    *state_refs,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
):
    """One (bh, q_block, kv_block) grid step of the online-softmax recurrence.

    ``state_refs`` is the (max, denom, accumulator) scratch, or nothing when
    the kv axis is one block: the step then starts from no state and writes
    its result straight out."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)
    q_start = q_idx * block_q
    k_start = kv_idx * block_k
    op = _operand_dtype(q_ref.dtype, causal)
    folded = _folds_scale(scale)

    def _step(state):
        """(m, l, acc) after this kv block, from ``state`` or from nothing."""
        q = q_ref[0]                              # [block_q, d]
        if folded:
            q = q.astype(jnp.float32) * scale
        k = k_ref[0].astype(op)                   # [block_k, d]
        v = v_ref[0].astype(op)                   # [block_k, d]
        logits = _dot(q.astype(op), k, 1, 1)      # [block_q, block_k]
        if not folded:
            logits = logits * scale
        if causal:
            keep = _causal_keep(logits.shape, q_start, k_start, 0)
            logits = jnp.where(keep, logits, NEG_INF)
        m_new = jnp.max(logits, axis=-1, keepdims=True)  # [block_q, 1]
        if state is not None:
            m_prev, l_prev, acc_prev = state
            m_new = jnp.maximum(m_prev, m_new)
        # Causal only: fully-masked rows keep m=-inf; exp against a safe max
        # stays 0. Without a mask every score is finite and so is m.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0) if causal else m_new
        p = jnp.exp(logits - m_safe)
        if causal:
            p = jnp.where(jnp.isfinite(logits), p, 0.0)
        l_new = jnp.sum(p, axis=-1, keepdims=True)
        acc = _dot(p.astype(op), v, 1, 0)
        if state is not None:
            corr = jnp.exp(m_prev - m_safe)
            if causal:
                corr = jnp.where(jnp.isfinite(m_prev), corr, 0.0)
            l_new = l_prev * corr + l_new
            acc = acc_prev * corr + acc
        return m_new, l_new, acc

    def _write(m, l, acc):
        denom = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc / denom).astype(o_ref.dtype)
        # Logsumexp per row, for the backward kernels' softmax recompute
        # (P = exp(logits - lse)). Causal: fully-masked rows keep -inf.
        lse = m + jnp.log(denom)
        if causal:
            lse = jnp.where(jnp.isfinite(m), lse, NEG_INF)
        lse_ref[0, 0] = lse[:, 0]

    if not state_refs:
        _write(*_step(None))
        return
    m_ref, l_ref, acc_ref = state_refs

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        m_new, l_new, acc = _step((m_ref[:, :1], l_ref[:, :1], acc_ref[:]))
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # A kv block strictly above the diagonal of this q block is all
        # masked; skip its matmuls entirely. Live iff this kv block
        # intersects the causal triangle of this q block.
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            _compute()

    else:
        _compute()

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        _write(m_ref[:, :1], l_ref[:, :1], acc_ref[:])


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

    # One kv block: no state runs from step to step, so none is kept.
    scratch_shapes = [] if nk == 1 else [
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


def _bwd_recompute(x, y, dx, dy, lse, delta, keep, scale):
    """Shared backward block math: recompute P from the forward's logsumexp
    and form dS — used identically by both backward kernels, each in its own
    orientation: the scores are ``x y^T`` and dP is ``dx dy^T``, so (q, k,
    do, v) gives [bq, bk] tiles against column ``lse``/``delta`` and (k, q,
    v, do) gives the transposed [bk, bq] tiles against row vectors.

    ``keep`` is the causal mask of the tile, or None; with it, masked and
    fully-masked rows are zeroed. The scale stays on the tiles here: out of
    them it measured no faster in either kernel (PERF.md, PR 30).

    Returns (p, ds): p = exp(logits - lse); ds = p * (dP - delta) * scale."""
    logits = _dot(x, y, 1, 1) * scale
    if keep is None:
        p = jnp.exp(logits - lse)
    else:
        logits = jnp.where(keep, logits, NEG_INF)
        p = jnp.where(jnp.isfinite(lse), jnp.exp(logits - lse), 0.0)
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
    return p, p * (_dot(dx, dy, 1, 1) - delta) * scale


def _bwd_dkdv_kernel(
    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, block_q: int, block_k: int, causal: bool, nq: int,
):
    """dK/dV for one kv block: grid (b*kv_head, kv_block, q_stream).

    Streams q/do/lse/delta blocks past a resident kv block, recomputing
    P^T = exp(k q^T - lse) from the forward's logsumexp on TRANSPOSED
    [bk, bq] tiles — lse/delta broadcast along lanes as they are stored, and
    dV += P^T dO, dK += dS^T Q are plain products (6.03 ms a call against
    6.34 contracting over the rows of untransposed tiles, PERF.md PR 30) —
    accumulating in VMEM scratch.

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
    op = _operand_dtype(q_ref.dtype, causal)

    def _compute():
        q = q_ref[0].astype(op)                   # [bq, d]
        do = do_ref[0].astype(op)                 # [bq, d]
        k = k_ref[0].astype(op)                   # [bk, d]
        v = v_ref[0].astype(op)                   # [bk, d]
        keep = (
            _causal_keep((block_k, block_q), q_start, k_start, 1)
            if causal else None
        )
        p_t, ds_t = _bwd_recompute(
            k, q, v, do, lse_ref[0], delta_ref[0], keep, scale
        )                                          # [bk, bq]
        dv_acc[:] = dv_acc[:] + _dot(p_t.astype(op), do, 1, 0)
        dk_acc[:] = dk_acc[:] + _dot(ds_t.astype(op), q, 1, 0)

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
    op = _operand_dtype(q_ref.dtype, causal)

    def _compute():
        q = q_ref[0].astype(op)
        do = do_ref[0].astype(op)
        k = k_ref[0].astype(op)
        v = v_ref[0].astype(op)
        keep = (
            _causal_keep((block_q, block_k), q_start, k_start, 0)
            if causal else None
        )
        _, ds = _bwd_recompute(
            q, k, do, v, lse_ref[0, 0][:, None], delta_ref[0, 0][:, None],
            keep, scale,
        )                                          # [bq, bk]
        dq_acc[:] = dq_acc[:] + _dot(ds.astype(op), k, 1, 0)

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


def _default_blocks(S: int, D: int, dtype, causal: bool, block_q, block_k,
                    backward: bool = False):
    """Resolve block sizes: as large as the 16 MiB of scoped VMEM allow, and
    what a v5e chip measured fastest (PERF.md, PR 30: kernel time of a call
    from the profiler's trace, bfloat16, 65,536 tokens of S 2048 unless
    said; VMEM is what the chip's compiler asks for the call).

    Forward without a mask, D <= 128: one kv block of up to 2048 keys and a
    q block that gives way to it (512), so that the float32 score tile
    stays at 4 MB. At S <= 2048 the recurrence is then a single step with
    no running state. ms at (1024, 1024) | (512, 2048) with state | without:
    D 64 4.83 | 4.03 | 3.77, D 128 2.48 | 2.19 | 2.09, D 32 9.93 | 8.83 |
    8.36; float32 D 64 4.90 | - | 3.89, D 128 2.49 | - | 2.10; S 4096, two
    kv blocks: 4.59 | 4.26. S 1024 in one 1024-key block: 5.02 -> 4.22.
    6.4 MiB (8.9 in float32).
    Forward with a mask: (1024, 1024) as before. A kv block that spans the
    sequence leaves the block skip nothing to skip: 4.42 ms against 4.90 at
    (512, 2048), and 3.52 against 4.22 at S 4096.
    Backward without a mask on bfloat16 operands, D <= 128: 1024 x 1024,
    11.9 MiB. ms at 512 | 1024 tiles: dK/dV D 64 6.88 | 6.24, D 128 3.33 |
    2.98, D 32 13.70 | 12.41; dQ D 64 5.37 | 4.71, D 128 2.61 | 2.25, D 32
    10.70 | 9.40 (2048 along either axis: within 1.5 % of that; 1024 x
    2048 does not fit).
    Backward with a mask or with float32 operands: 512, as before. 1024
    tiles ask 16.01 MiB with a mask (refused in a program of batch 32,
    accepted in one of batch 8: on the line) and 15.5 MiB in float32.
    D > 128: the caps of before (512, then 256), which no reading here
    covers beyond D 256 at those caps.
    The masked body at D 256 with grouped kv (PERF.md, PR 32: kernel time
    of a call from the trace of `train_q3next_s8192`, bfloat16,
    [2, 8192, 16:2, 256], 16,384 tokens; float32 operands under the mask):
    forward 14.85 ms at (512, 512), dK/dV 16.68 and dQ 12.65 at 512 tiles:
    39 % and 48 % of the causal half's roofline. No other tile was tried
    at that shape.
    """
    # Explicit blocks are clamped to the same caps: a block past them is a
    # compile error (1024-tile forwards fail Mosaic compilation at D=256),
    # not a knob, and a user-tuned forward tile must not push the backward's
    # larger working set (the logits, p, dp and ds tiles at once) past VMEM.
    if backward:
        narrow = _operand_dtype(dtype, causal) == jnp.bfloat16
        cap = 1024 if D <= 128 and narrow else (512 if D <= 256 else 256)
        bq = min(cap, S) if block_q is None else min(block_q, cap, S)
        bk = min(cap, S) if block_k is None else min(block_k, cap, S)
        return bq, bk
    cap_q = 1024 if D <= 128 else (512 if D <= 512 else 256)
    cap_k = 2048 if D <= 128 and not causal else cap_q
    bk = min(cap_k, S) if block_k is None else min(block_k, cap_k, S)
    # The [bq, bk] float32 score tile stays at the 4 MB of a 1024 x 1024 one.
    bq = min(cap_q, S, max(512, (1 << 20) // bk))
    return (bq if block_q is None else min(block_q, bq)), bk


def flash_can_tile(S: int, D: int) -> bool:
    """Whether the compiled forward AND backward kernels can tile seq len S
    at their default blocks — what the automatic routes (softmax->flash,
    ring, Ulysses) ask before selecting the kernel."""
    return all(
        None not in _tileable_blocks(
            S,
            *_default_blocks(S, D, dtype, causal, None, None, backward=backward),
        )
        for backward in (False, True)
        for dtype in (jnp.bfloat16, jnp.float32)
        for causal in (False, True)
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
    bq, bk = _default_blocks(
        q.shape[1], q.shape[-1], q.dtype, causal, block_q, block_k
    )
    return _flash_forward(q, k, v, s, causal, bq, bk, interpret)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    S, D = q.shape[1], q.shape[-1]
    bq, bk = _default_blocks(S, D, q.dtype, causal, block_q, block_k)
    # An S the backward cannot tile is refused here, while the forward is
    # traced, not halfway into the gradient's trace.
    _adjust_blocks(
        S,
        *_default_blocks(
            S, D, q.dtype, causal, block_q, block_k, backward=True
        ),
        interpret,
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
        q.shape[1], q.shape[-1], q.dtype, causal, block_q, block_k,
        backward=True,
    )
    return _flash_backward(
        q, k, v, out, lse, g, s, causal, bq, bk, interpret
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)

"""Ring attention: exact softmax attention over sequence-sharded inputs.

Long-context sequence parallelism, TPU-native. The sequence axis is sharded
over a mesh axis (``sp``); each device holds a query chunk and rotates
key/value chunks around the ring with ``jax.lax.ppermute`` (one ICI hop per
step) while maintaining flash-style online-softmax statistics, so

* memory per device is O(S/n * S/n) per step instead of O(S^2);
* communication is the K/V chunk per step, riding nearest-neighbor ICI links
  (the layout the TPU torus is built for) and overlapping with the block
  matmuls XLA schedules between permutes;
* the result is *exact* softmax attention — bitwise-independent of how many
  devices the sequence is sharded over (up to float associativity).

The reference has no long-context path at all (SURVEY.md §5: sequence length
capped at 2000 by a dense PE table, vanilla ``nn.MultiheadAttention`` at
`ray-tune-hpo-regression.py:139`); this module is the capability the TPU
framework adds so sequence length scales with the mesh instead of with HBM.

``ring_attention`` is differentiable (the loop is a ``lax.scan`` of jax ops;
ppermute has a transpose rule), so it drops straight into the sharded train
step for training over long sequences.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _ring_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool,
    scale: Optional[float],
) -> jnp.ndarray:
    """Per-device body; q: local [B, S/n, H, D] shard, k/v: [B, S/n, Hkv, D]
    (Hkv < H = grouped-query attention; kv chunks ROTATE at kv_heads, so the
    per-step ICI payload shrinks by the group factor — the broadcast to full
    heads happens only inside each step's local compute)."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    s = (D ** -0.5) if scale is None else scale

    qf = q.astype(jnp.float32) * s
    # Rotate kv blocks "down" the ring: after step i, this device holds the
    # shard originally owned by device (my_idx + i) mod n.
    perm = [(j, (j - 1) % n) for j in range(n)]

    q_pos = my_idx * Sq + jnp.arange(Sq)

    def step(carry, i):
        m, l, acc, k_cur, v_cur = carry
        src = (my_idx + i) % n
        k_pos = src * Sk + jnp.arange(Sk)

        if Hkv != H:
            k_loc = jnp.repeat(k_cur, H // Hkv, axis=2)
            v_loc = jnp.repeat(v_cur, H // Hkv, axis=2)
        else:
            k_loc, v_loc = k_cur, v_cur

        logits = jnp.einsum(
            "bqhd,bkhd->bqhk",
            qf,
            k_loc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if causal:
            cmask = q_pos[None, :, None, None] >= k_pos[None, None, None, :]
            logits = jnp.where(cmask, logits, -jnp.inf)

        m_new = jnp.maximum(m, logits.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, v_loc.astype(jnp.float32)
        )

        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    m0 = jnp.full((B, Sq, H), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n)
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash inner kernel: the ring's per-step block math through the Pallas MXU
# kernel (ops/pallas_attention.py) instead of a dense f32 einsum.
#
# Forward: each ring step runs the flash FORWARD on (my q chunk, visiting
# kv chunk), getting a chunk-normalized output plus its logsumexp; chunk
# outputs merge by logsumexp weighting (the same online-softmax algebra the
# kernel uses internally, applied across chunks), so the result is exact
# softmax attention over the full sequence.
#
# Backward: for a chunk pair, the flash backward evaluated with the GLOBAL
# logsumexp/output is exactly the global gradient's contribution from that
# pair (P = exp(logits - lse_global) are the true softmax weights). One ring
# pass computes everything: dq accumulates in place, while dk/dv partial
# accumulators ROTATE WITH their k/v chunks — after n steps every chunk is
# back at its owner carrying its fully-accumulated gradient.
#
# Causality never needs global positions inside the kernel: a visiting chunk
# is either entirely earlier (full attention), the diagonal (locally causal,
# since global row>=col iff local row>=col when offsets are equal), or
# entirely later (skipped) — a 3-way lax.switch around the existing kernels.
# ---------------------------------------------------------------------------


def _flash_chunk_fwd(q, k, v, scale, causal, interpret):
    """Chunk flash forward -> (out [B,S,H,D] normalized, lse [B*H,1,S])."""
    from distributed_machine_learning_tpu.ops.pallas_attention import (
        _default_blocks,
        _flash_forward,
    )

    S, D = q.shape[1], q.shape[-1]
    bq, bk = _default_blocks(S, D, q.dtype, causal, None, None)
    return _flash_forward(
        q, k, v, scale, causal, bq, bk, interpret, with_lse=True
    )


def _flash_chunk_bwd(q, k, v, out, lse, do, scale, causal, interpret,
                     q_side=None):
    """Chunk-pair flash backward with GLOBAL out/lse -> (dq, dk, dv).

    ``q_side``: precomputed (qb, dob, delta) — loop-invariant across the
    ring's k/v chunks, so the caller hoists it out of the scan."""
    from distributed_machine_learning_tpu.ops.pallas_attention import (
        _default_blocks,
        _flash_backward,
    )

    S, D = q.shape[1], q.shape[-1]
    bq, bk = _default_blocks(
        S, D, q.dtype, causal, None, None, backward=True
    )
    return _flash_backward(
        q, k, v, out, lse, do, scale, causal, bq, bk, interpret,
        q_side=q_side,
    )


def _lse_weights(lse_old, lse_new, lse_tot, B, H):
    """Merge weights exp(lse - lse_tot) for [B*H,1,S] lse, shaped to
    broadcast over [B, S, H, D] outputs; -inf rows contribute 0."""

    def w(lse):
        safe_tot = jnp.where(jnp.isfinite(lse_tot), lse_tot, 0.0)
        raw = jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe_tot), 0.0)
        bh, _, s = raw.shape
        return raw.reshape(B, H, s).transpose(0, 2, 1)[..., None]

    return w(lse_old), w(lse_new)


def _make_ring_flash(axis_name: str, causal: bool, scale: float,
                     interpret: bool):
    """Build the per-device flash-ring function with its custom VJP.

    A factory (rather than nondiff_argnums on a module-level function) so
    the closure carries the static config; jax caches tracing per factory
    call site, and _ring_local calls this once per trace.
    """

    def fwd_impl(q, k, v):
        n = jax.lax.psum(1, axis_name)
        my_idx = jax.lax.axis_index(axis_name)
        B, Sq, H, D = q.shape
        perm_n = [(j, (j - 1) % n) for j in range(n)]

        def chunk(q_, k_, v_, causal_flag):
            return _flash_chunk_fwd(q_, k_, v_, scale, causal_flag, interpret)

        def step(carry, i):
            acc, lse, k_cur, v_cur = carry
            src = (my_idx + i) % n

            def do_full(_):
                return chunk(q, k_cur, v_cur, False)

            def do_diag(_):
                return chunk(q, k_cur, v_cur, True)

            def do_skip(_):
                return (
                    jnp.zeros_like(q),
                    jnp.full((B * H, 1, Sq), -jnp.inf, jnp.float32),
                )

            if causal:
                branch = jnp.where(src == my_idx, 1,
                                   jnp.where(src < my_idx, 0, 2))
                out_i, lse_i = jax.lax.switch(
                    branch, (do_full, do_diag, do_skip), None
                )
            else:
                out_i, lse_i = do_full(None)

            lse_new = jnp.logaddexp(lse, lse_i)
            w_old, w_i = _lse_weights(lse, lse_i, lse_new, B, H)
            acc = acc * w_old + out_i.astype(jnp.float32) * w_i

            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm_n)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm_n)
            return (acc, lse_new, k_nxt, v_nxt), None

        acc0 = jnp.zeros(q.shape, jnp.float32)
        lse0 = jnp.full((B * H, 1, Sq), -jnp.inf, jnp.float32)
        (acc, lse, _, _), _ = jax.lax.scan(
            step, (acc0, lse0, k, v), jnp.arange(n)
        )
        return acc.astype(q.dtype), lse

    @jax.custom_vjp
    def ring_flash(q, k, v):
        out, _ = fwd_impl(q, k, v)
        return out

    def ring_flash_fwd(q, k, v):
        out, lse = fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def ring_flash_bwd(res, g):
        from distributed_machine_learning_tpu.ops.pallas_attention import (
            _to_bh,
        )

        q, k, v, out, lse = res
        do = g
        n = jax.lax.psum(1, axis_name)
        my_idx = jax.lax.axis_index(axis_name)
        perm = [(j, (j - 1) % n) for j in range(n)]
        # Loop-invariant q side, hoisted out of the scan: the transposes
        # and the delta reduction would otherwise repeat per ring step.
        qb, dob, ob = _to_bh(q), _to_bh(do), _to_bh(out)
        delta = jnp.sum(
            dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1
        )[:, None, :]
        q_side = (qb, dob, delta)

        def step(carry, i):
            dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
            src = (my_idx + i) % n

            def pair(causal_flag):
                return _flash_chunk_bwd(
                    q, k_cur, v_cur, out, lse, do, scale, causal_flag,
                    interpret, q_side=q_side,
                )

            def do_full(_):
                return pair(False)

            def do_diag(_):
                return pair(True)

            def do_skip(_):
                return (jnp.zeros_like(q), jnp.zeros_like(k_cur),
                        jnp.zeros_like(v_cur))

            if causal:
                branch = jnp.where(src == my_idx, 1,
                                   jnp.where(src < my_idx, 0, 2))
                dq_i, dk_i, dv_i = jax.lax.switch(
                    branch, (do_full, do_diag, do_skip), None
                )
            else:
                dq_i, dk_i, dv_i = do_full(None)

            dq_acc = dq_acc + dq_i.astype(jnp.float32)
            # dk/dv partials travel WITH their chunk: after n rotations the
            # chunk (and its fully-summed gradient) is back at its owner.
            dk_cur = dk_cur + dk_i.astype(jnp.float32)
            dv_cur = dv_cur + dv_i.astype(jnp.float32)
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
            dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
            return (dq_acc, k_nxt, v_nxt, dk_nxt, dv_nxt), None

        dq0 = jnp.zeros(q.shape, jnp.float32)
        (dq, _, _, dk, dv), _ = jax.lax.scan(
            step,
            (dq0, k, v, jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32)),
            jnp.arange(n),
        )
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    ring_flash.defvjp(ring_flash_fwd, ring_flash_bwd)
    return ring_flash


def _use_flash_inner(mode, Sq: int, Sk: int, D: int) -> bool:
    """Resolve the use_flash knob: 'auto' = the same gate as the
    softmax->flash route (models/layers.py) on TPU, for a chunk length the
    kernel can tile.

    The flash chunk kernels assume equal q/kv chunk lengths (self-
    attention over one sharded sequence); cross-length rings stay on the
    dense path (auto) or are rejected (forced True).
    """
    if mode not in ("auto", True, False):
        # bool('false') is True — reject strings so a config typo can't
        # silently force the kernel path.
        raise ValueError(
            f"use_flash must be 'auto', True, or False; got {mode!r}"
        )
    if mode == "auto":
        from distributed_machine_learning_tpu.ops.pallas_attention import (
            flash_can_tile,
        )

        return (
            jax.default_backend() == "tpu"
            and Sq == Sk and Sq >= 1024 and D <= 64
            and flash_can_tile(Sq, D)
        )
    if mode and Sq != Sk:
        raise ValueError(
            f"use_flash=True needs equal q/kv sequence lengths per shard "
            f"(got {Sq} vs {Sk}); the dense ring handles cross-length"
        )
    return mode


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axis: Optional[str] = "dp",
    head_axis: Optional[str] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash="auto",
    flash_interpret: bool = False,
) -> jnp.ndarray:
    """Exact softmax attention with the sequence sharded over ``axis_name``.

    q: [B, S, H, D]; k, v: [B, S, Hkv, D] with ``H % Hkv == 0`` —
    grouped-query attention is native on BOTH inner paths: kv chunks rotate
    the ring at kv_heads (per-step ICI payload shrinks by the group factor);
    the dense path broadcasts only inside each step's local compute, and the
    flash path streams grouped kv straight through the Pallas kernels.
    Global arrays (S divisible by the axis size).
    ``batch_axis`` optionally shards batch over a second mesh axis (dp);
    ``head_axis`` optionally shards heads over a third (tp) — heads are
    independent, so tensor parallelism composes with the ring for free.
    Returns [B, S, H, D] with the same sharding.

    ``use_flash``: run each ring step's block attention through the Pallas
    flash kernel instead of the dense einsum — ``"auto"`` (default) picks
    it in the kernel's measured-win regime (TPU, local chunk >= 1024,
    head_dim <= 64); True/False force it. ``flash_interpret`` runs the
    kernels in the Pallas interpreter (CPU tests).
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.axis_names}")
    baxis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    haxis = head_axis if (head_axis and head_axis in mesh.axis_names) else None
    if (
        haxis
        and k.shape[2] != q.shape[2]  # grouped kv only; full-head q and kv
        # failing to divide the axis is the ordinary sharding error
        and k.shape[2] % mesh.shape[haxis] != 0
    ):
        raise ValueError(
            f"grouped kv ({k.shape[2]} heads) cannot shard over head axis "
            f"{haxis!r} (size {mesh.shape[haxis]}); broadcast kv to full "
            f"heads first (models/layers.py does this automatically)"
        )
    spec = P(baxis, axis_name, haxis, None)
    n_shards = mesh.shape[axis_name]
    local_S, D = q.shape[1] // n_shards, q.shape[-1]
    local_Sk = k.shape[1] // n_shards
    if _use_flash_inner(use_flash, local_S, local_Sk, D):
        s = (D ** -0.5) if scale is None else scale

        def local(q_, k_, v_):
            return _make_ring_flash(
                axis_name, causal, s, flash_interpret
            )(q_, k_, v_)

        fn = jax.shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return fn(q, k, v)
    fn = jax.shard_map(
        partial(_ring_local, axis_name=axis_name, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)

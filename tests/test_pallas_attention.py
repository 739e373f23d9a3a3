"""Pallas flash-attention kernel vs the dense XLA reference (interpret mode).

On CPU the kernel runs through the Pallas interpreter — numerics identical to
the compiled Mosaic path, so correctness (online-softmax recurrence, causal
block skipping, custom-scale plumbing, the recompute VJP) is what's tested
here; MXU throughput is bench territory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_machine_learning_tpu.ops import pallas_attention as pa
from distributed_machine_learning_tpu.ops.attention import dot_product_attention
from distributed_machine_learning_tpu.ops.pallas_attention import flash_attention

B, S, H, D = 1, 32, 2, 8
BQ = BK = 16
F32 = jnp.float32


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


def test_matches_dense_softmax_attention(qkv):
    q, k, v = qkv
    out = flash_attention(q, k, v, block_q=BQ, block_k=BK, interpret=True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_default_blocks_path(qkv):
    """block_q/block_k=None — the production default (_default_blocks picks
    the tile size, clamped by S and head dim)."""
    q, k, v = qkv
    out = flash_attention(q, k, v, interpret=True)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # Gradient through the default path too (custom_vjp default resolution).
    g = jax.grad(
        lambda q: jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)
    )(q)
    g_ref = jax.grad(
        lambda q: jnp.sum(dot_product_attention(q, k, v) ** 2)
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)


def test_explicit_blocks_clamped_to_measured_caps():
    """User-pinned tiles are clamped to the measured Mosaic-compilable caps
    in BOTH directions (ADVICE r2): block 1024 at D=256 fails Mosaic on the
    forward, so an explicit 1024 must come back as the 512 cap, not a
    compile error at trace time."""
    from distributed_machine_learning_tpu.ops.pallas_attention import (
        _default_blocks,
    )

    f32, bf16 = jnp.float32, jnp.bfloat16
    # Forward: D=256 caps at 512 even when the user asks for 1024.
    assert _default_blocks(4096, 256, f32, False, 1024, 1024) == (512, 512)
    # D<=128 honors an explicit 1024.
    assert _default_blocks(4096, 64, f32, False, 1024, 1024) == (1024, 1024)
    # Backward holds its own caps against explicit blocks: 1024 for the
    # body without a mask on bfloat16 operands (11.9 MiB of the 16 MiB of
    # scoped VMEM at D 64), 512 with a mask (1024 tiles: 16.01 MiB) or with
    # float32 operands (15.5 MiB).
    bwd = dict(backward=True)
    assert _default_blocks(4096, 64, bf16, False, 2048, 2048, **bwd) == (1024, 1024)
    assert _default_blocks(4096, 64, bf16, True, 1024, 1024, **bwd) == (512, 512)
    assert _default_blocks(4096, 64, f32, False, 1024, 1024, **bwd) == (512, 512)
    assert _default_blocks(4096, 256, bf16, False, 1024, 1024, **bwd) == (512, 512)
    assert _default_blocks(4096, 512, f32, False, 1024, 1024, **bwd) == (256, 256)
    # Sequence length still bounds everything.
    assert _default_blocks(128, 64, f32, False, 1024, None) == (128, 128)
    # Defaults: without a mask the kv block spans up to 2048 and the q block
    # gives way, so that the float32 score tile stays at 4 MB; with one, a
    # kv block that spanned the sequence would leave nothing to skip.
    assert _default_blocks(2048, 64, bf16, False, None, None) == (512, 2048)
    assert _default_blocks(4096, 64, f32, False, 2048, 2048) == (512, 2048)
    assert _default_blocks(1024, 64, bf16, False, None, None) == (1024, 1024)
    assert _default_blocks(2048, 64, bf16, True, None, None) == (1024, 1024)
    assert _default_blocks(4096, 256, bf16, False, None, None) == (512, 512)


def test_causal_matches_masked_dense(qkv):
    q, k, v = qkv
    out = flash_attention(
        q, k, v, causal=True, block_q=BQ, block_k=BK, interpret=True
    )
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_custom_scale_key_dim_scaling(qkv):
    """The reference's dead key_dim_scaling knob (SURVEY.md §2 C19), live here."""
    q, k, v = qkv
    scale = float(D) ** -0.75
    out = flash_attention(
        q, k, v, scale=scale, block_q=BQ, block_k=BK, interpret=True
    )
    ref = dot_product_attention(q, k, v, scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_block_size_invariance(qkv):
    q, k, v = qkv
    a = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True)
    b = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_gradients_match_dense(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=BQ, block_k=BK, interpret=True
            )
            ** 2
        )

    def loss_ref(q, k, v):
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        return jnp.sum(dot_product_attention(q, k, v, mask=mask) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_model_layer_flash_attention_type(qkv):
    """attention_type='flash' works in MultiHeadAttention (off-TPU it routes
    to the scan-based blockwise path with the same math)."""
    import flax.linen as nn

    from distributed_machine_learning_tpu.models.layers import MultiHeadAttention

    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 32, 16)), jnp.float32)
    for attn_type in ("flash", "scaled_dot_product"):
        m = MultiHeadAttention(
            d_model=16, num_heads=2, attention_type=attn_type, block_size=16
        )
        variables = m.init({"params": jax.random.key(0)}, x)
        out = m.apply(variables, x, deterministic=True)
        assert out.shape == x.shape
        if attn_type == "flash":
            flash_out = out
        else:
            np.testing.assert_allclose(
                np.asarray(flash_out), np.asarray(out), atol=1e-5
            )


@pytest.mark.parametrize("bq,bk", [(16, 8), (8, 16)])
def test_gradients_mismatched_blocks(qkv, bq, bk):
    """The two backward kernels have independent per-axis block logic
    (separate causal live-conditions, opposite grid orderings) — exercised
    with block_q != block_k, causal."""
    q, k, v = qkv

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
            ) ** 2
        )

    def loss_ref(q, k, v):
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        return jnp.sum(dot_product_attention(q, k, v, mask=mask) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_gradients_bfloat16(qkv):
    """The backward kernels' bf16 cast path produces usable gradients."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)

    g = jax.grad(
        lambda q: jnp.sum(
            flash_attention(
                q, k, v, block_q=BQ, block_k=BK, interpret=True
            ).astype(jnp.float32) ** 2
        )
    )(q)
    assert g.dtype == jnp.bfloat16
    g_ref = jax.grad(
        lambda q: jnp.sum(
            dot_product_attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
            ) ** 2
        )
    )(q.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(g, np.float32), np.asarray(g_ref), atol=5e-2
    )


def test_softmax_to_flash_routing_gate(monkeypatch):
    """Long-sequence softmax attention on TPU reroutes to the flash kernel
    (same math); short sequences, big heads, and non-TPU backends don't."""
    from distributed_machine_learning_tpu.models import layers

    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    assert layers._route_softmax_to_flash(1024, 64)
    assert layers._route_softmax_to_flash(4096, 32)
    assert not layers._route_softmax_to_flash(512, 64)    # short: XLA wins
    assert not layers._route_softmax_to_flash(2048, 128)  # fwd measured slower
    monkeypatch.setattr(layers, "_on_tpu", lambda: False)
    assert not layers._route_softmax_to_flash(4096, 64)


def test_bfloat16_inputs(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    out = flash_attention(q, k, v, block_q=BQ, block_k=BK, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dot_product_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2
    )


class TestGroupedQueryAttention:
    """Native GQA (VERDICT r3 next #4): k/v stay at kv_heads through the
    forward stream AND the backward's grouped dK/dV accumulation — exactness
    is against the jnp.repeat broadcast path."""

    @pytest.fixture(scope="class")
    def gqa_qkv(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(2, S, 4, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, S, 2, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, S, 2, D)), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_repeat(self, gqa_qkv, causal):
        q, k, v = gqa_qkv
        out = flash_attention(q, k, v, None, causal, BQ, BK, True)
        kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        ref = flash_attention(q, kr, vr, None, causal, BQ, BK, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_repeat(self, gqa_qkv, causal):
        """dk/dv come back at kv_heads shape, equal to the repeat path's
        group-summed gradients (what jax.grad through jnp.repeat computes)."""
        q, k, v = gqa_qkv

        def loss_gqa(q, k, v):
            return jnp.sum(
                jnp.sin(flash_attention(q, k, v, None, causal, BQ, BK, True))
            )

        def loss_rep(q, k, v):
            kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
            return jnp.sum(
                jnp.sin(flash_attention(q, kr, vr, None, causal, BQ, BK, True))
            )

        g = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
        r = jax.grad(loss_rep, argnums=(0, 1, 2))(q, k, v)
        assert g[1].shape == k.shape and g[2].shape == v.shape
        for a, b in zip(g, r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_multi_query_single_kv_head(self, gqa_qkv):
        q, k, v = gqa_qkv
        k1, v1 = k[:, :, :1], v[:, :, :1]  # MQA: one kv head
        out = flash_attention(q, k1, v1, None, False, BQ, BK, True)
        kr, vr = jnp.repeat(k1, 4, axis=2), jnp.repeat(v1, 4, axis=2)
        ref = flash_attention(q, kr, vr, None, False, BQ, BK, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_invalid_kv_heads_rejected(self, gqa_qkv):
        q, _, _ = gqa_qkv
        k3 = jnp.zeros((q.shape[0], S, 3, D), jnp.float32)  # 4 % 3 != 0
        with pytest.raises(ValueError, match="multiple"):
            flash_attention(q, k3, k3, None, False, BQ, BK, True)


# --------------------------------------------------------------------------
# The bodies without dead guards, the scale out of the tile, operands in the
# input's dtype, dK/dV on transposed scores: the same mathematics as the body
# below, which is how the three kernels read before (every score guarded, the
# scale on the [bq, bk] tile twice, float32 operands, dK/dV contracting over
# the tile's row axis). It lives here as the reference only.
# --------------------------------------------------------------------------

def _rows_last(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )


def _guarded_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                        m_ref, l_ref, acc_ref, *, scale):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q, k, v = (r[0].astype(F32) for r in (q_ref, k_ref, v_ref))
    logits = _rows_last(q, k) * scale
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        m = m_ref[:, :1]
        lse = jnp.where(jnp.isfinite(m), m + jnp.log(denom), -jnp.inf)
        lse_ref[0, 0] = lse[:, 0]


def _guarded_p_ds(x, y, dx, dy, lse, delta, scale):
    """p and ds as they were formed, in the orientation x and y give."""
    logits = _rows_last(x, y) * scale
    p = jnp.where(jnp.isfinite(lse), jnp.exp(logits - lse), 0.0)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    return p, p * (_rows_last(dx, dy) - delta) * scale


def _guarded_dkdv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, scale, transposed):
    pid = pl.program_id(2)

    @pl.when(pid == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, do, k, v = (r[0].astype(F32) for r in (q_ref, do_ref, k_ref, v_ref))
    if transposed:
        p, ds = _guarded_p_ds(
            k, q, v, do, lse_ref[0], delta_ref[0], scale
        )                                           # [bk, bq]
        contract = (((1,), (0,)), ((), ()))
    else:
        p, ds = _guarded_p_ds(
            q, k, do, v, lse_ref[0, 0][:, None], delta_ref[0, 0][:, None],
            scale,
        )                                           # [bq, bk]
        contract = (((0,), (0,)), ((), ()))
    dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
        p, do, contract, preferred_element_type=F32
    )
    dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
        ds, q, contract, preferred_element_type=F32
    )

    @pl.when(pid == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _guarded_dq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_acc, *, scale):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q, do, k, v = (r[0].astype(F32) for r in (q_ref, do_ref, k_ref, v_ref))
    _, ds = _guarded_p_ds(
        q, k, do, v, lse_ref[0, 0][:, None], delta_ref[0, 0][:, None], scale
    )
    dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=F32
    )

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _guarded_attention(qb, kb, vb, dob, scale, bq, bk, transposed):
    """(out, lse, dq, dk, dv) over [B*H, S, D] arrays by the guarded body."""
    BH, S_, D_ = qb.shape
    nq, nk = S_ // bq, S_ // bk
    rows = lambda n: pl.BlockSpec((1, n, D_), lambda b, i, j: (b, i, 0))
    cols = lambda n: pl.BlockSpec((1, n, D_), lambda b, i, j: (b, j, 0))
    vec_i = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    vec_j = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    out, lse = pl.pallas_call(
        functools.partial(_guarded_fwd_kernel, scale=scale),
        grid=(BH, nq, nk),
        in_specs=[rows(bq), cols(bk), cols(bk)],
        out_specs=[rows(bq), vec_i],
        out_shape=[like(qb), jax.ShapeDtypeStruct((BH, 1, S_), F32)],
        scratch_shapes=[pltpu.VMEM((bq, 128), F32), pltpu.VMEM((bq, 128), F32),
                        pltpu.VMEM((bq, D_), F32)],
        interpret=True,
    )(qb, kb, vb)
    delta = jnp.sum(dob.astype(F32) * out.astype(F32), axis=-1)[:, None, :]
    dk, dv = pl.pallas_call(
        functools.partial(
            _guarded_dkdv_kernel, scale=scale, transposed=transposed
        ),
        grid=(BH, nk, nq),
        in_specs=[cols(bq), cols(bq), vec_j, vec_j, rows(bk), rows(bk)],
        out_specs=[rows(bk), rows(bk)],
        out_shape=[like(kb), like(vb)],
        scratch_shapes=[pltpu.VMEM((bk, D_), F32), pltpu.VMEM((bk, D_), F32)],
        interpret=True,
    )(qb, dob, lse, delta, kb, vb)
    dq = pl.pallas_call(
        functools.partial(_guarded_dq_kernel, scale=scale),
        grid=(BH, nq, nk),
        in_specs=[cols(bk), cols(bk), rows(bq), rows(bq), vec_i, vec_i],
        out_specs=rows(bq),
        out_shape=like(qb),
        scratch_shapes=[pltpu.VMEM((bq, D_), F32)],
        interpret=True,
    )(kb, vb, qb, dob, lse, delta)
    return out, lse, dq, dk, dv


def _op_by_op(fn, *args):
    """Run ``fn`` with XLA's fusion pass off, so that every operation rounds
    on its own. The CPU backend contracts a multiply and an add that share a
    fusion into one fused multiply-add, and which operations share a fusion
    changes when a guard between them goes: that is one rounding of the
    compiler's, not of the kernel's arithmetic, and it is what 'bit for bit'
    has to be held apart from here."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"}
    )(*args)


@pytest.mark.parametrize("scale", [None, 0.125, 0.3])
@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
def test_noncausal_float32_is_bit_for_bit_the_guarded_body(qkv, bq, bk, scale):
    """Without a mask and with finite inputs every guard is the identity,
    and a scale that leaves the forward's tile (0.125 folds into q; 0.3 and 8**-0.5
    stay on the scores) multiplies exactly: output, lse and dQ carry the
    guarded body's bits. dK/dV carry the bits of the guarded body on the
    same transposed tiles; against the untransposed one the same terms are
    summed by another product, whose order the backend chooses, so there
    the comparison is to a few units in the last place. (16, 32) spans the
    kv axis in one block: the forward without running state."""
    q, k, v = qkv
    do = jnp.asarray(
        np.random.default_rng(7).normal(size=q.shape), jnp.float32
    )
    s = D ** -0.5 if scale is None else scale

    def new(q, k, v, do):
        out, lse = pa._flash_forward(
            q, k, v, s, False, bq, bk, True, with_lse=True
        )
        dq, dk, dv = pa._flash_backward(
            q, k, v, out, lse, do, s, False, bq, bk, True
        )
        return tuple(pa._to_bh(x) for x in (out, dq, dk, dv)) + (lse,)

    def guarded(transposed):
        def fn(q, k, v, do):
            out, lse, dq, dk, dv = _guarded_attention(
                *(pa._to_bh(x) for x in (q, k, v, do)), s, bq, bk, transposed
            )
            return out, dq, dk, dv, lse
        return fn

    got = _op_by_op(new, q, k, v, do)
    same_tiles = _op_by_op(guarded(True), q, k, v, do)
    as_before = _op_by_op(guarded(False), q, k, v, do)
    for name, a, b in zip(("out", "dq", "dk", "dv", "lse"), got, same_tiles):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    for name, a, b in zip(("out", "dq", "dk", "dv", "lse"), got, as_before):
        if name in ("dk", "dv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=0, atol=2e-6, err_msg=name
            )
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def _norm_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _dense_narrowing(q, k, v, do, scale, causal):
    """Dense attention and its gradients with bfloat16 operands into every
    product and float32 everywhere else: p and ds are narrowed at the two
    places the kernels narrow them (p into p v and p^T dO, ds into ds k and
    ds^T q), and the softmax statistics never are."""
    bf = jnp.bfloat16
    ein = functools.partial(jnp.einsum, preferred_element_type=F32)
    logits = ein("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        keep = jnp.tril(jnp.ones(logits.shape[-2:], bool))
        logits = jnp.where(keep, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p_un = jnp.exp(logits - m)
    l = jnp.sum(p_un, axis=-1, keepdims=True)
    out = (
        ein("bhqk,bkhd->bqhd", p_un.astype(bf), v)
        / l[..., 0].transpose(0, 2, 1)[..., None]
    ).astype(bf)
    p = jnp.exp(logits - (m + jnp.log(l)))
    delta = jnp.sum(do.astype(F32) * out.astype(F32), axis=-1)  # [B, S, H]
    dp = ein("bqhd,bkhd->bhqk", do, v)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None]) * scale
    dv = ein("bhqk,bqhd->bkhd", p.astype(bf), do).astype(bf)
    dk = ein("bhqk,bqhd->bkhd", ds.astype(bf), q).astype(bf)
    dq = ein("bhqk,bkhd->bqhd", ds.astype(bf), k).astype(bf)
    return out, dq, dk, dv


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("scale", [0.125, 0.3], ids=["folded", "on_tile"])
def test_bfloat16_operands_match_a_dense_reference_that_narrows_alike(
    qkv, scale, causal
):
    """bfloat16 inputs reach the products as they are and p, ds are
    narrowed to bfloat16 there: tight (1e-2 normalised) against a dense
    reference that narrows at the same places, and against float32 dense
    inside what the chip showed for the float32-operand kernels against the
    same reference (1.2e-2 to 1.4e-2 normalised at [8, 2048, 8, 64], PR 25),
    so the narrower operands cost nothing that comparison could see."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    do = jnp.asarray(
        np.random.default_rng(7).normal(size=q.shape), jnp.bfloat16
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, scale, causal, BQ, BK, True)

    out, vjp = jax.vjp(flash, q, k, v)
    got = (out,) + vjp(do)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    alike = _dense_narrowing(q, k, v, do, scale, causal)

    mask = jnp.tril(jnp.ones((S, S), bool))[None, None] if causal else None
    wide = [x.astype(F32) for x in (q, k, v)]
    ref_out, ref_vjp = jax.vjp(
        lambda q, k, v: dot_product_attention(q, k, v, mask=mask, scale=scale),
        *wide,
    )
    dense = (ref_out,) + ref_vjp(do.astype(F32))
    for name, g, a, d in zip(("out", "dq", "dk", "dv"), got, alike, dense):
        assert _norm_gap(g, a) < 1e-2, (name, _norm_gap(g, a))
        assert _norm_gap(g, d) < 1.4e-2, (name, _norm_gap(g, d))


def test_nonfinite_input_reaches_the_output_without_a_mask(qkv):
    """No mask, no guard: a NaN in one q row is a NaN in that row's output
    and lse (the guarded body wrote zeros there), and nowhere else."""
    q, k, v = qkv
    q = q.at[0, 3, 1, 0].set(jnp.nan)
    out, lse = pa._flash_forward(
        q, k, v, D ** -0.5, False, BQ, BK, True, with_lse=True
    )
    out, lse = np.asarray(out), np.asarray(lse).reshape(B, H, S)
    bad = np.zeros((B, S, H), bool)
    bad[0, 3, 1] = True
    assert np.isnan(out[bad]).all() and np.isfinite(out[~bad]).all()
    assert np.isnan(lse[0, 1, 3])
    assert np.isfinite(np.delete(lse[0, 1], 3)).all()


def test_causal_row_with_nothing_to_attend_keeps_lse_minus_inf(qkv):
    """The causal body keeps its guards: a row whose every visible score is
    -inf (row 0 sees key 0 alone, and key 0 scores -inf) comes out as zeros
    with lse = -inf, which is what the ring's merge of chunk results takes
    for 'no weight'; rows that see other keys too stay finite, and so do
    dK and dV, which the backward's guards on that row's lse keep clean
    (dQ is ds k, and 0 x -inf in k is the input's own NaN)."""
    q, k, v = qkv
    q = q.at[..., 0].set(jnp.abs(q[..., 0]) + 1.0)
    k = k.at[:, 0, :, 0].set(-jnp.inf)
    out, lse = pa._flash_forward(
        q, k, v, D ** -0.5, True, BQ, BK, True, with_lse=True
    )
    lse = np.asarray(lse).reshape(B, H, S)
    assert np.all(np.isneginf(lse[:, :, 0]))
    assert np.isfinite(lse[:, :, 1:]).all()
    assert np.all(np.asarray(out)[:, 0] == 0.0)
    assert np.isfinite(np.asarray(out)).all()
    _, dk, dv = pa._flash_backward(
        q, k, v, out, jnp.asarray(lse.reshape(B * H, 1, S)), jnp.ones_like(q),
        D ** -0.5, True, BQ, BK, True,
    )
    assert np.isfinite(np.asarray(dk)).all()
    assert np.isfinite(np.asarray(dv)).all()

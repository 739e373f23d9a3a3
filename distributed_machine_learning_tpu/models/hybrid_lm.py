"""A decoder language model of gated-delta and gated-attention layers over
a dropless mixture of experts (family ``gated_hybrid_lm``).

Pre-norm blocks ``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``.  The
mixer is a Gated DeltaNet (``ops/gated_delta.py``) except in every
``full_attention_interval``-th layer, where it is causal softmax attention
with grouped key/value heads, rotary positions on part of the head, q/k
norms and a sigmoid output gate.  Every block's feed-forward is a top-k
mixture of gated experts plus one gated shared expert; the layer is told
which experts it holds, routes over all of them and computes its own share
(``ops/grouped_experts.py``).  Token embedding in, final norm and an untied
head out; no biases.  Inputs are int32 ids ``[B, S]``, outputs logits
``[B, S, vocab]`` in the compute dtype.

Matrix products take the compute dtype (``dtype``); parameters, the
norms, the router, softmax, the decay and the recurrent state are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.models import layers
from distributed_machine_learning_tpu.models.layers import apply_rope
from distributed_machine_learning_tpu.models.moe import STATS_COLLECTION
from distributed_machine_learning_tpu.ops.gated_delta import (
    DEFAULT_CHUNK,
    chunk_gated_delta_rule,
)
from distributed_machine_learning_tpu.ops.grouped_experts import (
    DEFAULT_TILE,
    make_plan,
    routed_experts,
)

NORM_EPS = 1e-6
# Every projection, the embedding, the head, the router and the experts
# start from normal(INIT_STD).
INIT_STD = 0.02
_normal_init = nn.initializers.normal(INIT_STD)


def _dense(features: int, name: str, dtype):
    return nn.Dense(
        features, use_bias=False, name=name, dtype=dtype,
        param_dtype=jnp.float32, kernel_init=_normal_init,
    )


def _rms(x, eps: float = NORM_EPS):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)


def _l2(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)``, ``w`` from nought, in
    float32; the result in ``dtype``."""

    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        return (_rms(x) * (1.0 + w)).astype(self.dtype or x.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # softplus^-1 of a step drawn log-uniformly from [1e-3, 1e-1].
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)
    ))
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(key, shape, dtype=jnp.float32):
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def causal_depthwise_conv(x, weight):
    """y[t, c] = sum_j weight[c, j] * x[t - (W - 1) + j, c], nought before
    the sequence; x [B, S, C], weight [C, W]; float32 out."""
    width = weight.shape[-1]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(
        xp[:, j:j + S].astype(jnp.float32) * weight[:, j].astype(jnp.float32)
        for j in range(width)
    )


class GatedDeltaNetMixer(nn.Module):
    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_width: int = 4
    chunk: int = DEFAULT_CHUNK
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        hk, hv = self.num_key_heads, self.num_value_heads
        dk, dv = self.key_head_dim, self.value_head_dim
        key_dim, value_dim = hk * dk, hv * dv
        dtype = self.dtype or x.dtype

        qkvz = _dense(2 * key_dim + 2 * value_dim, "in_proj_qkvz", dtype)(x)
        ba = _dense(2 * hv, "in_proj_ba", dtype)(x)
        conv_w = self.param("conv_weight", _conv_init,
                            (2 * key_dim + value_dim, self.conv_width))
        a_log = self.param("A_log", _a_log_init, (hv,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (hv,))
        norm_w = self.param("norm_weight", nn.initializers.ones, (dv,),
                            jnp.float32)

        mixed, z = jnp.split(qkvz, [2 * key_dim + value_dim], axis=-1)
        mixed = jax.nn.silu(causal_depthwise_conv(mixed, conv_w)).astype(dtype)
        q, k, v = jnp.split(mixed, [key_dim, 2 * key_dim], axis=-1)
        q = q.reshape(B, S, hk, dk)
        k = k.reshape(B, S, hk, dk)
        v = v.reshape(B, S, hv, dv)
        # Each key head serves hv // hk value heads in a row.
        q = jnp.repeat(_l2(q.astype(jnp.float32)) * dk ** -0.5, hv // hk, axis=2)
        k = jnp.repeat(_l2(k.astype(jnp.float32)), hv // hk, axis=2)
        b, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

        with jax.named_scope("gated_delta"):
            o = chunk_gated_delta_rule(
                q, k, v, g, beta, chunk=self.chunk, matmul_dtype=dtype
            )
        z = z.reshape(B, S, hv, dv).astype(jnp.float32)
        o = (norm_w * _rms(o) * jax.nn.silu(z)).astype(dtype)
        return _dense(D, "out_proj", dtype)(o.reshape(B, S, value_dim))


def causal_attention(q, k, v, scale: float):
    """Plain causal softmax attention with grouped key/value heads, for
    backends without the kernels: q [B, S, H, D], k and v [B, S, Hkv, D]."""
    B, S, H, D = q.shape
    group = H // k.shape[2]
    qg = q.reshape(B, S, k.shape[2], group, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, D).astype(q.dtype)


def causal_attention_on_device(q, k, v, scale: float):
    """Causal attention under the scope ``causal_attention``: the flash
    kernels on a TPU, ``causal_attention`` elsewhere."""
    with jax.named_scope("causal_attention"):
        if not layers._on_tpu():
            return causal_attention(q, k, v, scale)
        # Asked for by name: the automatic route stops at head size 64
        # (models/layers.py).
        from distributed_machine_learning_tpu.ops.pallas_attention import (
            flash_attention,
        )

        return flash_attention(q, k, v, scale, True)


class GatedAttentionMixer(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float = 1e7
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        H, Hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dtype = self.dtype or x.dtype
        qg = _dense(H * 2 * hd, "q_proj", dtype)(x)
        q, gate = jnp.split(qg.reshape(B, S, H, 2 * hd), 2, axis=-1)
        k = _dense(Hkv * hd, "k_proj", dtype)(x)
        v = _dense(Hkv * hd, "v_proj", dtype)(x)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        q = ZeroCentredRMSNorm(name="q_norm", dtype=dtype)(q)
        k = ZeroCentredRMSNorm(name="k_norm", dtype=dtype)(k)
        r = self.rotary_dim
        q, k = (
            jnp.concatenate(
                [apply_rope(a[..., :r], base=self.rope_theta), a[..., r:]],
                axis=-1,
            )
            for a in (q, k)
        )
        out = causal_attention_on_device(q, k, v, hd ** -0.5)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dtype)
        return _dense(D, "o_proj", dtype)(out.reshape(B, S, H * hd))


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``."""

    width: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        dtype = self.dtype or x.dtype
        g = _dense(self.width, "gate_proj", dtype)(x)
        u = _dense(self.width, "up_proj", dtype)(x)
        return _dense(x.shape[-1], "down_proj", dtype)(jax.nn.silu(g) * u)


class DroplessMoE(nn.Module):
    """Top-k routing over ``num_experts`` experts of which this layer holds
    ``held_experts`` (first id, count): its own experts' part of the result
    plus the shared expert, which every holder computes whole.

    ``router`` is the part that turns float32 tokens ``[T, d]`` into logits
    ``[T, num_experts]``: any module, adopted under the name ``router``;
    left out, one bias-free matrix.  The weighting rule: a chosen expert's
    weight is its softmax probability over all experts, divided by the sum
    of the token's ``top_k`` probabilities where ``renormalise`` (at
    ``top_k`` 1 that quotient is the constant 1 and the router gets no
    gradient), as it is where not.  ``shared_width`` 0 builds no shared
    expert and no gate for it."""

    num_experts: int
    top_k: int
    expert_width: int
    shared_width: int
    held_experts: Optional[tuple] = None
    tile: int = DEFAULT_TILE
    dtype: Any = None
    router: Optional[nn.Module] = None
    renormalise: bool = True

    @nn.compact
    def __call__(self, x):
        B, S, D = x.shape
        E, K, F = self.num_experts, self.top_k, self.expert_width
        first, held = self.held_experts or (0, E)
        if not (0 <= first and first + held <= E and held > 0 and K <= E):
            raise ValueError(
                f"held_experts {self.held_experts} / top_k {K} do not fit "
                f"{E} experts"
            )
        dtype = self.dtype or x.dtype
        tokens = x.reshape(B * S, D)
        w_gate = self.param("w_gate", _normal_init, (held, D, F), jnp.float32)
        w_up = self.param("w_up", _normal_init, (held, D, F), jnp.float32)
        w_down = self.param("w_down", _normal_init, (held, F, D), jnp.float32)
        router = self.router if self.router is not None else nn.Dense(
            E, use_bias=False, name="router", dtype=jnp.float32,
            param_dtype=jnp.float32, kernel_init=_normal_init,
        )

        with jax.named_scope("routed_experts"):
            logits = router(tokens.astype(jnp.float32))
            probs = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = jax.lax.top_k(probs, K)
            if self.renormalise:
                top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            plan = make_plan(top_e, first, held, self.tile)
            routed = routed_experts(
                tokens.astype(dtype), top_p, w_gate, w_up, w_down, plan,
                self.tile,
            )
        if not self.is_initializing():
            # ``init`` returns whatever is sown, and counts among its
            # results keep the whole forward pass in the initialisation's
            # program (87 MB of code for the chip at S 8192, 46 s to
            # compile, run with every trial's start).  With nothing sown
            # its results are the parameters and the pass is dead code.
            sizes = plan.sizes.astype(jnp.float32)
            self.sow(STATS_COLLECTION, "local_pairs", sizes.sum())
            self.sow(STATS_COLLECTION, "load_max_over_mean",
                     sizes.max() / jnp.maximum(sizes.mean(), 1e-9))
        if not self.shared_width:
            return routed.astype(dtype).reshape(B, S, D)

        with jax.named_scope("shared_expert"):
            shared = GatedMLP(self.shared_width, dtype, name="shared_expert")(
                tokens.astype(dtype)
            )
            shared_gate = nn.Dense(
                1, use_bias=False, name="shared_expert_gate", dtype=dtype,
                param_dtype=jnp.float32, kernel_init=_normal_init,
            )(tokens.astype(dtype))
            shared = shared.astype(jnp.float32) * jax.nn.sigmoid(
                shared_gate.astype(jnp.float32)
            )
        return (routed + shared).astype(dtype).reshape(B, S, D)


@dataclasses.dataclass(frozen=True)
class HybridSizes:
    """The widths of one block; the defaults are no model's."""

    d_model: int
    full_attention_interval: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 64
    rotary_dim: int = 16
    rope_theta: float = 1e7
    linear_key_heads: int = 2
    linear_value_heads: int = 4
    linear_key_head_dim: int = 32
    linear_value_head_dim: int = 32
    conv_width: int = 4
    delta_chunk: int = DEFAULT_CHUNK
    num_experts: int = 8
    top_k: int = 2
    expert_width: int = 64
    shared_width: int = 64
    held_experts: Optional[tuple] = None
    expert_tile: int = DEFAULT_TILE


class HybridBlock(nn.Module):
    """``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``.  Each half is
    rematerialised by itself in the backward pass, so that only one half's
    intermediates are held at a time."""

    sizes: HybridSizes
    full_attention: bool
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        c, dtype = self.sizes, self.dtype

        @nn.remat
        def mixer_half(block, x):
            if block.full_attention:
                mixer = GatedAttentionMixer(
                    c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim,
                    c.rope_theta, dtype, name="attention",
                )
            else:
                mixer = GatedDeltaNetMixer(
                    c.linear_key_heads, c.linear_value_heads,
                    c.linear_key_head_dim, c.linear_value_head_dim,
                    c.conv_width, c.delta_chunk, dtype,
                    name="linear_attention",
                )
            norm = ZeroCentredRMSNorm(name="input_norm", dtype=dtype)
            return x + mixer(norm(x))

        @nn.remat
        def moe_half(block, h):
            moe = DroplessMoE(
                c.num_experts, c.top_k, c.expert_width, c.shared_width,
                c.held_experts, c.expert_tile, dtype, name="moe",
            )
            norm = ZeroCentredRMSNorm(name="post_norm", dtype=dtype)
            return h + moe(norm(h))

        return moe_half(self, mixer_half(self, x))


class GatedHybridLM(nn.Module):
    vocab_size: int
    num_layers: int
    sizes: HybridSizes
    dtype: Any = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True):
        del deterministic  # no dropout anywhere
        c = self.sizes
        dtype = self.dtype or jnp.float32
        embed = self.param(
            "embed_tokens", _normal_init,
            (self.vocab_size, c.d_model), jnp.float32,
        )
        x = embed.astype(dtype)[tokens.astype(jnp.int32)]
        for i in range(self.num_layers):
            full = (i + 1) % c.full_attention_interval == 0
            x = HybridBlock(c, full, dtype, name=f"layer_{i}")(x)
        x = ZeroCentredRMSNorm(name="final_norm", dtype=dtype)(x)
        with jax.named_scope("lm_head"):
            return _dense(self.vocab_size, "lm_head", dtype)(x)

"""A decoder language model of compressed convolutional attention over a
top-1 mixture of experts behind an MLP router (family ``cca_moe_lm``).

Pre-norm blocks ``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))`` with a
plain RMS norm (a learned scale from 1).  The mixer projects to a latent of
``num_heads`` query and ``num_kv_heads`` key heads, mixes queries and keys
together through two causal convolutions over positions (one depth-wise,
one that mixes the channels inside a head), adds the mean of the
pre-convolution queries and keys across each group, L2-normalises both to
``sqrt(head_dim)`` with a learned temperature a key head, turns part of
each head by rotary positions, and attends causally over values of which
the second head is the previous position's.  The feed-forward is
``DroplessMoE`` (``models/hybrid_lm.py``) with ``RouterMLP`` as its router,
the unrenormalised top-k probability as the weight and no shared expert.
Token embedding in, final norm out, and the embedding's transpose is the
head.  Inputs are int32 ids ``[B, S]``, outputs logits ``[B, S, vocab]`` in
the compute dtype.

Matrix products take the compute dtype (``dtype``); parameters, norms, the
router, softmax, the convolutions' sums, the L2 normalisation and the
temperature are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_machine_learning_tpu.models.hybrid_lm import (
    DroplessMoE,
    _dense,
    _normal_init,
    _rms,
    causal_attention_on_device,
)
from distributed_machine_learning_tpu.models.layers import apply_rope
from distributed_machine_learning_tpu.models.moe import STATS_COLLECTION
from distributed_machine_learning_tpu.ops.grouped_experts import DEFAULT_TILE

NORM_EPS = 1e-5


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * w``, ``w`` from 1, in float32; the
    result in ``dtype``."""

    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
        return (_rms(x, NORM_EPS) * w).astype(self.dtype or x.dtype)


def _fan_in_uniform(fan_in: int):
    """uniform(-fan_in ** -1/2, fan_in ** -1/2): a convolution's weight and
    bias as torch starts them."""
    bound = fan_in ** -0.5

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def shift(x, by: int = 1):
    """``y[t] = x[t - by]``, nought before the sequence: x [B, S, ...]."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (by, 0)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def causal_conv(x, weight, bias):
    """Depth-wise causal convolution over positions: ``y[t, c] = bias[c] +
    sum_j weight[c, j] * x[t - (W - 1) + j, c]``; x [B, S, C] float32,
    weight [C, W]."""
    width = weight.shape[-1]
    return bias + sum(
        shift(x, width - 1 - j) * weight[:, j] for j in range(width)
    )


def causal_head_conv(x, weight, bias, dtype):
    """Causal convolution over positions that mixes the channels inside a
    head: ``y[t, g] = bias[g] + sum_j x[t - (W - 1) + j, g] @ weight[g, j]``;
    x [B, S, G, D], weight [G, W, D, D], bias [G, D]; products in ``dtype``,
    float32 out."""
    width = weight.shape[1]
    xd, wd = x.astype(dtype), weight.astype(dtype)
    return bias + sum(
        jnp.einsum("bsgd,gde->bsge", shift(xd, width - 1 - j), wd[:, j],
                   preferred_element_type=jnp.float32)
        for j in range(width)
    )


def _unit(x, length: float):
    """x at the L2 norm ``length`` along its last axis."""
    return x * (length * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)))


class CCAMixer(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float = 5e6
    conv_time0: int = 2
    conv_time1: int = 2
    dtype: Any = None

    @nn.compact
    def __call__(self, h):
        B, S, D = h.shape
        H, Hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        group, G = H // Hkv, H + Hkv
        dtype = self.dtype or h.dtype
        with jax.named_scope("cca_mix"):
            q0 = _dense(H * hd, "q_proj", dtype)(h).astype(jnp.float32)
            k0 = _dense(Hkv * hd, "k_proj", dtype)(h).astype(jnp.float32)
            init0 = _fan_in_uniform(self.conv_time0)
            init1 = _fan_in_uniform(self.conv_time1 * hd)
            conv0_w = self.param("conv0_weight", init0, (G * hd, self.conv_time0))
            conv0_b = self.param("conv0_bias", init0, (G * hd,))
            conv1_w = self.param("conv1_weight", init1,
                                 (G, self.conv_time1, hd, hd))
            conv1_b = self.param("conv1_bias", init1, (G * hd,))
            tau = self.param("temperature", nn.initializers.ones, (Hkv,),
                             jnp.float32)

            mixed = causal_conv(jnp.concatenate([q0, k0], -1), conv0_w, conv0_b)
            mixed = causal_head_conv(
                mixed.reshape(B, S, G, hd), conv1_w, conv1_b.reshape(G, hd),
                dtype,
            )
            q0 = q0.reshape(B, S, Hkv, group, hd)
            k0 = k0.reshape(B, S, Hkv, hd)
            q = mixed[:, :, :H].reshape(q0.shape) + 0.5 * (q0 + k0[:, :, :, None])
            k = mixed[:, :, H:] + 0.5 * (jnp.mean(q0, axis=3) + k0)
            q = _unit(q.reshape(B, S, H, hd), hd ** 0.5)
            k = _unit(k, hd ** 0.5) * tau[:, None]
            r = self.rotary_dim
            q, k = (
                jnp.concatenate(
                    [apply_rope(a[..., :r], base=self.rope_theta), a[..., r:]],
                    axis=-1,
                ).astype(dtype)
                for a in (q, k)
            )
            # Half of the value channels from this position, half from
            # the one before: at two key-value heads, a head each.
            half = Hkv * hd // 2
            v = jnp.concatenate(
                [_dense(half, "v_proj", dtype)(h),
                 _dense(half, "v_shift_proj", dtype)(shift(h))], axis=-1
            ).reshape(B, S, Hkv, hd)
        out = causal_attention_on_device(q, k, v, hd ** -0.5)
        with jax.named_scope("cca_mix"):
            return _dense(D, "o_proj", dtype)(out.reshape(B, S, H * hd))


class RouterMLP(nn.Module):
    """Router logits over ``num_experts`` from a small MLP, float32
    throughout: a projection down to ``hidden`` with a bias, an RMS norm,
    ``depth`` layers ``gelu(r W + b)`` of that width, a projection out
    without a bias."""

    num_experts: int
    hidden: int
    depth: int = 2

    @nn.compact
    def __call__(self, tokens):
        def dense(features, name, use_bias=True):
            # ``highest``: at its default a float32 product on the chip is
            # one bfloat16 pass, and a top-1 choice turns on the last digits.
            return nn.Dense(
                features, use_bias=use_bias, name=name, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=_normal_init,
                precision=jax.lax.Precision.HIGHEST,
            )

        with jax.named_scope("router_mlp"):
            r = RMSNorm(name="norm")(dense(self.hidden, "down")(tokens))
            for i in range(self.depth):
                r = jax.nn.gelu(dense(self.hidden, f"hidden_{i}")(r),
                                approximate=False)
            return dense(self.num_experts, "out", use_bias=False)(r)


@dataclasses.dataclass(frozen=True)
class CCASizes:
    """The widths of one block; the defaults are no model's."""

    d_model: int
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rotary_dim: int = 16
    rope_theta: float = 5e6
    conv_time0: int = 2
    conv_time1: int = 2
    num_experts: int = 8
    top_k: int = 1
    expert_width: int = 64
    router_hidden: int = 16
    held_experts: Optional[tuple] = None
    expert_tile: int = DEFAULT_TILE


class CCABlock(nn.Module):
    """``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``, each half
    rematerialised by itself in the backward pass (as ``HybridBlock``).
    Called as a scan's body: ``(x, None) -> (y, None)``."""

    sizes: CCASizes
    dtype: Any = None

    @nn.compact
    def __call__(self, x, _=None):
        c, dtype = self.sizes, self.dtype

        @nn.remat
        def mixer_half(block, x):
            mixer = CCAMixer(
                c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim,
                c.rope_theta, c.conv_time0, c.conv_time1, dtype,
                name="attention",
            )
            return x + mixer(RMSNorm(name="input_norm", dtype=dtype)(x))

        @nn.remat
        def moe_half(block, h):
            moe = DroplessMoE(
                c.num_experts, c.top_k, c.expert_width, 0, c.held_experts,
                c.expert_tile, dtype,
                # No parent: the expert layer adopts it, as ``moe/router``.
                router=RouterMLP(c.num_experts, c.router_hidden, parent=None),
                renormalise=False, name="moe",
            )
            return h + moe(RMSNorm(name="post_norm", dtype=dtype)(h))

        return moe_half(self, mixer_half(self, x)), None


class CCAMoELM(nn.Module):
    """Every layer is of the one kind, so the stack is a scan over the
    layers' stacked parameters (``layers/...`` leaves with the layer as
    their first axis): one traced and compiled body whatever the depth,
    a third of the unrolled stack's code at six layers."""

    vocab_size: int
    num_layers: int
    sizes: CCASizes
    dtype: Any = None

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True):
        del deterministic  # no dropout anywhere
        dtype = self.dtype or jnp.float32
        embed = self.param(
            "embed_tokens", _normal_init,
            (self.vocab_size, self.sizes.d_model), jnp.float32,
        ).astype(dtype)
        x = embed[tokens.astype(jnp.int32)]
        stack = nn.scan(
            CCABlock, variable_axes={"params": 0, STATS_COLLECTION: 0},
            split_rngs={"params": True}, length=self.num_layers,
        )
        x, _ = stack(self.sizes, dtype, name="layers")(x, None)
        x = RMSNorm(name="final_norm", dtype=dtype)(x)
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x, embed)

"""Building-block layers for the model zoo (flax.linen).

TPU-native re-designs of the reference's layer components, with its latent bugs
fixed and its intended-but-unimplemented knobs made real (SURVEY.md §2 C7-C10):

* ``PositionalEncoding`` — sin/cos table built at the right rank (the reference
  built a 2-D buffer and indexed it 3-D, `ray-tune-hpo-regression.py:40-43,53`).
* ``MultiHeadAttention`` — one module covering the reference's attention
  registry (`:138-145`): softmax ("scaled_dot_product" / "multi_head_attention"),
  true O(n) "linear_attention", and "blockwise" for long sequences, with a real
  ``key_dim_scaling`` exponent (C19's dead knob).
* ``DepthwiseSeparableFF`` — depthwise + pointwise conv feed-forward with an
  output projection back to d_model (the reference omitted it, so its residual
  add shape-mismatched, `:69,:176`).
* ``StochasticDepth`` — per-sample residual-branch drop (C19's dead
  ``stochastic_depth_rate`` knob, implemented).
* ``EncoderLayer`` — post-LN block matching `CustomEncoderLayer` (`:122-178`).
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_machine_learning_tpu.models.moe import MoEFF
from distributed_machine_learning_tpu.ops.attention import (
    blockwise_attention,
    dot_product_attention,
    largest_divisor_block,
    linear_attention,
)

ATTENTION_TYPES = (
    "scaled_dot_product",
    "multi_head_attention",
    "linear_attention",
    "blockwise",
    "flash",
)


def resolve_remat_policy(name):
    """A ``jax.checkpoint_policies`` policy from its config name.

    Accepted: None/""/"none" (no policy — full remat when remat is on) or
    any attribute of ``jax.checkpoint_policies`` ("dots_saveable",
    "nothing_saveable", "everything_saveable",
    "dots_with_no_batch_dims_saveable", ...).  The knob that trades
    recompute FLOPs against activation HBM per block — wired from
    ``config["remat_policy"]`` (docs/performance.md).
    """
    if name is None or name in ("", "none", False):
        return None
    policy = getattr(jax.checkpoint_policies, str(name), None)
    if policy is None:
        valid = sorted(
            n for n in dir(jax.checkpoint_policies) if not n.startswith("_")
        )
        raise ValueError(
            f"Unknown remat policy {name!r}; expected one of {valid}"
        )
    return policy


def activation_spec(mesh: Mesh, shape, *axes) -> P:
    """A per-dim mesh-axis intent cleaned against an activation's shape:
    axes the mesh lacks or whose size does not divide the dim drop to None
    (same reconciliation rule as ``parallel.partition.clean_spec``,
    duplicated here so the model zoo never imports the parallel package at
    module level)."""
    cleaned = []
    for dim, axis in zip(shape, axes):
        if (
            axis is None
            or mesh is None
            or axis not in mesh.axis_names
            or int(dim) % int(mesh.shape[axis]) != 0
        ):
            cleaned.append(None)
        else:
            cleaned.append(axis)
    return P(*cleaned)


def constrain_activation(x: jnp.ndarray, mesh: Optional[Mesh], *axes):
    """Pin an activation's layout at a block boundary (residual stream,
    attention q/k/v) with ``with_sharding_constraint``.

    Without the pin, GSPMD is free to resolve the layout from whichever
    neighboring op it propagates first — on dp×tp meshes that can
    materialize a replicated [B, S, H, D] attention intermediate or bounce
    the residual stream through an unnecessary all-gather.  No-op without
    a mesh (single-device / unsharded paths build models with mesh=None).
    """
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, activation_spec(mesh, x.shape, *axes))
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _partitioned(mesh: Optional[Mesh]) -> bool:
    """A mesh of more than one device makes the program a partitioned
    (GSPMD) one, and the compiler refuses a bare Mosaic kernel there
    ("cannot be automatically partitioned"): only the seq_axis paths,
    which wrap the kernel in shard_map, may run it under such a mesh."""
    return mesh is not None and mesh.size > 1


def _route_softmax_to_flash(seq_len: int, head_dim: int) -> bool:
    """Whether a plain softmax attention call should run the Pallas flash
    kernel instead: same exact math (online softmax). Gated to S >= 1024 at
    head_dim <= 64 and to lengths the kernel can tile; this route also
    serves eval — configs wanting flash at bigger head dims select
    attention_type='flash' explicitly.

    Read on a v5e chip on today's code (PERF.md, PR 30: all device time of
    a call, layout copies included, bf16, 65,536 tokens), XLA's attention |
    the kernels, ms. head_dim 64 forward: S 512 2.58 | 2.78, S 1024 5.06 |
    3.35, S 2048 9.36 | 5.01, S 4096 12.64 | 9.26; forward + backward: 8.50
    | 7.64, 17.34 | 10.74, 31.96 | 17.10, 60.97 | 31.62. head_dim 128, 4
    heads, forward: 1.71 | 1.48, 2.99 | 1.84, 5.09 | 3.09, 9.75 | 5.48;
    forward + backward: 5.75 | 3.87, 9.72 | 5.27, 17.74 | 9.22, 33.43 |
    16.75. So inside the gate the kernels are 1.4 to 1.9 times faster, and
    the gate is narrower than it need be (head_dim 128, and S 512 where the
    backward runs): widening it moves which program a model trains with,
    and is its own change."""
    from distributed_machine_learning_tpu.ops.pallas_attention import (
        flash_can_tile,
    )

    return (
        _on_tpu() and seq_len >= 1024 and head_dim <= 64
        and flash_can_tile(seq_len, head_dim)
    )


def sincos_position_table(max_len: int, d_model: int) -> np.ndarray:
    """Classic transformer sin/cos positional table, shape [max_len, d_model]."""
    position = np.arange(max_len, dtype=np.float32)[:, None]
    div_term = np.exp(
        np.arange(0, d_model, 2, dtype=np.float32) * (-np.log(10000.0) / d_model)
    )
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(position * div_term)
    table[:, 1::2] = np.cos(position * div_term[: d_model // 2])
    return table


class PositionalEncoding(nn.Module):
    """Adds a fixed sin/cos positional table, then dropout.

    Parity: `PositionalEncoding` (`ray-tune-hpo-regression.py:25-54`), with the
    2-D/3-D indexing bug fixed and the table stored as a module constant (it is
    not a parameter; no need to carry it in the checkpoint).
    """

    d_model: int
    dropout_rate: float = 0.1
    max_len: int = 5000

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        table = jnp.asarray(sincos_position_table(self.max_len, self.d_model))
        # Match x's dtype: under bf16 compute an f32 table would promote the
        # whole residual stream back to f32, silently undoing mixed precision.
        x = x + table[None, : x.shape[1], :].astype(x.dtype)
        return nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)


def apply_rope(x: jnp.ndarray, base: float = 10000.0,
               positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Rotary position embedding over the head dim of [B, S, H, D].

    Rotate-half convention: pairs (x[..., :D/2], x[..., D/2:]) rotate by
    position-dependent angles, so q·k depends only on RELATIVE distance —
    the long-context-friendly alternative to the additive sin/cos table
    (no max_len table, extrapolates past training lengths, and composes
    with sequence sharding: the rotation is elementwise per position, so
    GSPMD shards it with the activations). Math in f32, cast back.
    """
    B, S, H, D = x.shape
    if D % 2:
        raise ValueError(f"RoPE needs an even head dim, got {D}")
    half = D // 2
    pos = (jnp.arange(S, dtype=jnp.float32)
           if positions is None else positions.astype(jnp.float32))
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos[:, None] * freqs[None, :]            # [S, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


class StochasticDepth(nn.Module):
    """Drops an entire residual branch per sample with prob ``rate`` at train time."""

    rate: float

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        if self.rate <= 0.0 or deterministic:
            return x
        keep = 1.0 - self.rate
        rng = self.make_rng("dropout")
        mask_shape = (x.shape[0],) + (1,) * (x.ndim - 1)
        mask = jax.random.bernoulli(rng, keep, mask_shape)
        return jnp.where(mask, x / keep, 0.0)


class MultiHeadAttention(nn.Module):
    """Self-attention with a selectable scoring kernel.

    ``attention_type``:
      - "scaled_dot_product" / "multi_head_attention": softmax attention
        (the reference routed both names to torch ``nn.MultiheadAttention``,
        `:138-143`).
      - "linear_attention": true O(n) kernelized linear attention — the
        reference's intent at `:87-117`, minus its O(n^2) scoring and unused
        head args.
      - "blockwise": flash-style blocked softmax for long sequences.

    ``key_dim_scaling`` generalizes the 1/sqrt(d) logit scale to
    d ** -key_dim_scaling (reference's dead C19 knob).
    """

    d_model: int
    num_heads: int
    attention_type: str = "scaled_dot_product"
    key_dim_scaling: float = 0.5
    dropout_rate: float = 0.0
    causal: bool = False
    # None = let each kernel pick its measured-fastest block size (the
    # Pallas flash kernel defaults to large 1024 tiles; the lax.scan
    # blockwise path to 128). An explicit value pins both.
    block_size: Optional[int] = None
    # Sequence parallelism: when set (with a mesh), softmax attention runs
    # sequence-sharded over this mesh axis — the long-context path.
    # Requires the surrounding jit to shard x's sequence dim over `seq_axis`.
    # `seq_parallel_mode` picks the strategy: "ring" (ppermute K/V rotation,
    # parallel/ring_attention.py) or "ulysses" (all_to_all head/seq
    # reshuffle, parallel/ulysses.py — needs divisible head counts).
    seq_axis: Optional[str] = None
    seq_parallel_mode: str = "ring"
    batch_axis: Optional[str] = "dp"
    head_axis: Optional[str] = "tp"
    mesh: Optional[Mesh] = None
    # Compute dtype for projections (params stay float32). The attention
    # kernels themselves already run their softmax/accumulation in float32
    # and cast back to q.dtype (ops/attention.py, ops/pallas_attention.py).
    dtype: Optional[jnp.dtype] = None
    # Rotary position embedding on q/k (relative positions inside the
    # attention scores — the long-context alternative to the model-level
    # additive sin/cos table; see TransformerRegressor.position_encoding).
    rope: bool = False
    # Grouped-query attention: project k/v to this many heads (must divide
    # num_heads) and share each kv head across a query group. None = full
    # MHA; 1 = multi-query. Cuts k/v PROJECTION params/FLOPs by
    # num_heads/num_kv_heads on every path. The Pallas flash kernel (both
    # the explicit "flash" type and the softmax->flash auto-route), the
    # blockwise scan (grouped einsums), ring attention (kv rotates the ring
    # grouped), and Ulysses (when the head split divides) consume kv at
    # kv_heads NATIVELY, with the grouped dK/dV reduction inside the flash
    # backward kernel (ops/pallas_attention.py); linear attention shares
    # per-kv-head state across each query group. Only the dense einsum
    # path broadcasts, just before the kernel (XLA fuses that repeat).
    num_kv_heads: Optional[int] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        if self.attention_type not in ATTENTION_TYPES:
            raise ValueError(
                f"Unknown attention_type {self.attention_type!r}; "
                f"expected one of {ATTENTION_TYPES}"
            )
        if self.d_model % self.num_heads != 0:
            raise ValueError(
                f"d_model={self.d_model} not divisible by num_heads={self.num_heads}"
            )
        kv_heads = self.num_kv_heads if self.num_kv_heads is not None else self.num_heads
        if kv_heads <= 0 or self.num_heads % kv_heads != 0:
            # Explicit > 0 check: 0 would silently mean full MHA via
            # truthiness, and negatives pass Python's sign-following modulo
            # (4 % -2 == 0) into an opaque DenseGeneral shape error.
            raise ValueError(
                f"num_kv_heads={kv_heads} must be a positive divisor of "
                f"num_heads={self.num_heads}"
            )
        head_dim = self.d_model // self.num_heads
        B, S, _ = x.shape

        def proj(name, heads):
            return nn.DenseGeneral(
                features=(heads, head_dim), axis=-1, name=name,
                dtype=self.dtype,
            )(x)

        q = proj("query", self.num_heads)
        k = proj("key", kv_heads)
        v = proj("value", kv_heads)
        if self.seq_axis is None:
            # Attention-boundary pins (dp×tp meshes): heads over head_axis,
            # batch over batch_axis — with head-sharded projection kernels
            # this keeps the whole attention block head-local so GSPMD
            # never materializes a replicated [B, S, H, D] intermediate.
            # The seq-parallel paths (ring/ulysses) own their layouts.
            q = constrain_activation(
                q, self.mesh, self.batch_axis, None, self.head_axis, None
            )
            k = constrain_activation(
                k, self.mesh, self.batch_axis, None, self.head_axis, None
            )
            v = constrain_activation(
                v, self.mesh, self.batch_axis, None, self.head_axis, None
            )

        def full_kv(k, v):
            # Broadcast each kv head over its query group for paths WITHOUT
            # native grouped-kv support; the flash and ring paths below skip
            # this and stream kv at kv_heads (see attribute comment).
            if kv_heads != self.num_heads:
                group = self.num_heads // kv_heads
                return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
            return k, v

        if self.rope:
            # Applied to the GLOBAL [B, S, H, D] arrays before any
            # sequence-parallel entry — elementwise per position, so GSPMD
            # shards it with the activations and every downstream kernel
            # (dense/flash/ring/ulysses) sees already-rotated q/k.
            q, k = apply_rope(q), apply_rope(k)

        if self.seq_axis is not None:
            if self.mesh is None:
                raise ValueError(
                    "seq_axis set but no mesh given: ring attention needs the "
                    "device mesh to shard the sequence over"
                )
            if self.attention_type not in (
                "scaled_dot_product", "multi_head_attention", "flash",
                "blockwise",
            ):
                # Ring attention computes exact softmax attention; silently
                # substituting it for a different kernel (e.g. linear
                # attention) would change the math the config asked for.
                raise ValueError(
                    f"attention_type={self.attention_type!r} cannot run "
                    f"sequence-parallel: ring attention implements softmax "
                    f"attention only. Drop seq_axis or use a softmax variant."
                )
            if self.seq_parallel_mode == "ulysses":
                from distributed_machine_learning_tpu.parallel.ulysses import (
                    ulysses_attention as seq_parallel_attention,
                )

                # Ulysses all-to-alls redistribute HEADS over the sp (and
                # tp) axes; grouped kv rides them at kv_heads (all-to-all
                # payload / group) when the split divides, else broadcast.
                # head_split is ulysses' own rule — one definition, no
                # drift; seq_axis membership is validated downstream.
                from distributed_machine_learning_tpu.parallel.ulysses import (
                    head_split,
                )

                if kv_heads % head_split(
                    self.mesh, self.seq_axis, self.head_axis
                ) != 0:
                    k, v = full_kv(k, v)
            elif self.seq_parallel_mode == "ring":
                from distributed_machine_learning_tpu.parallel.ring_attention import (
                    ring_attention as seq_parallel_attention,
                )
                # Ring attention takes kv at kv_heads natively: chunks
                # rotate the ring at the grouped size (ICI payload / group).
                # UNLESS tensor parallelism shards the head axis and the kv
                # head count doesn't divide over it (e.g. MQA's 1 kv head on
                # tp=2) — then grouped kv cannot be laid out on the mesh and
                # the broadcast is required for correctness.
                if (
                    self.head_axis
                    and self.head_axis in self.mesh.axis_names
                    and kv_heads % self.mesh.shape[self.head_axis] != 0
                ):
                    k, v = full_kv(k, v)
            else:
                raise ValueError(
                    f"Unknown seq_parallel_mode {self.seq_parallel_mode!r}; "
                    f"expected 'ring' or 'ulysses'"
                )

            scale = float(head_dim) ** (-self.key_dim_scaling)
            out = seq_parallel_attention(
                q, k, v,
                mesh=self.mesh,
                axis_name=self.seq_axis,
                batch_axis=self.batch_axis,
                head_axis=self.head_axis,
                causal=self.causal,
                scale=scale,
            )
        elif self.attention_type == "linear_attention":
            # linear attention consumes grouped kv natively (per-kv-head
            # state shared across each query group).
            out = linear_attention(q, k, v, causal=self.causal)
        elif self.attention_type == "flash":
            # Hand-written Pallas MXU kernel on TPU; off-TPU the same math
            # runs through the lax.scan blockwise path (Mosaic kernels only
            # compile for TPU backends).
            scale = float(head_dim) ** (-self.key_dim_scaling)
            if _on_tpu():
                if _partitioned(self.mesh):
                    raise ValueError(
                        f"attention_type='flash' under a {dict(self.mesh.shape)} "
                        f"mesh: a Mosaic kernel cannot be partitioned "
                        f"automatically; set seq_axis (ring/Ulysses run the "
                        f"kernel inside shard_map) or use another "
                        f"attention_type"
                    )
                from distributed_machine_learning_tpu.ops.pallas_attention import (
                    flash_attention,
                )

                # Block clamping/divisor adjustment happens inside
                # flash_attention (None = its measured-fastest defaults);
                # kv stays at kv_heads — the kernel streams it grouped.
                out = flash_attention(
                    q, k, v, scale=scale, causal=self.causal,
                    block_q=self.block_size, block_k=self.block_size,
                )
            else:
                bs = largest_divisor_block(S, self.block_size or 128)
                q_scaled = q * (scale / (float(head_dim) ** -0.5))
                # blockwise consumes grouped kv natively (grouped einsums).
                out = blockwise_attention(
                    q_scaled, k, v, block_size=bs, causal=self.causal
                )
        elif self.attention_type == "blockwise":
            bs = largest_divisor_block(S, self.block_size or 128)
            out = blockwise_attention(q, k, v, block_size=bs, causal=self.causal)
        else:
            scale = float(head_dim) ** (-self.key_dim_scaling)
            if _route_softmax_to_flash(S, head_dim) and not _partitioned(
                self.mesh
            ):
                # Exact same softmax math through the measured-faster
                # Pallas kernel (long sequences on TPU only). Blocks stay
                # None — the kernel's measured-fastest tiles; block_size
                # here is the blockwise-scan knob, and a small value would
                # turn the fast path into a slow one (a 256-key block: the
                # forward call 18.4 ms against 6.2, PERF.md PR 30).
                from distributed_machine_learning_tpu.ops.pallas_attention import (
                    flash_attention,
                )

                out = flash_attention(
                    q, k, v, scale=scale, causal=self.causal,
                )
            else:
                k, v = full_kv(k, v)
                mask = None
                if self.causal:
                    mask = jnp.tril(jnp.ones((S, S), bool))[None, None, :, :]
                out = dot_product_attention(q, k, v, mask=mask, scale=scale)

        out = nn.DenseGeneral(
            features=self.d_model, axis=(-2, -1), name="out",
            dtype=self.dtype,
        )(out)
        return nn.Dropout(self.dropout_rate)(out, deterministic=deterministic)


class LinearFF(nn.Module):
    """Linear -> ReLU -> Linear feed-forward (`ray-tune-hpo-regression.py:151-155`)."""

    d_model: int
    dim_feedforward: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Dense(self.dim_feedforward, dtype=self.dtype)(x)
        x = nn.relu(x)
        return nn.Dense(self.d_model, dtype=self.dtype)(x)


class DepthwiseSeparableFF(nn.Module):
    """Depthwise (k=3) + pointwise conv feed-forward, projected back to d_model.

    Parity: `DepthwiseSeparableConv` (`ray-tune-hpo-regression.py:59-82`) with
    the missing d_model output projection added so the residual add is always
    shape-correct (the reference only worked when dim_feedforward == d_model).
    flax convs are NWC (batch, seq, channels) natively — no transpose dance.
    """

    d_model: int
    dim_feedforward: int
    kernel_size: int = 3
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Conv(
            features=self.d_model,
            kernel_size=(self.kernel_size,),
            padding="SAME",
            feature_group_count=self.d_model,
            name="depthwise",
            dtype=self.dtype,
        )(x)
        x = nn.Conv(
            features=self.dim_feedforward, kernel_size=(1,), name="pointwise",
            dtype=self.dtype,
        )(x)
        x = nn.relu(x)
        return nn.Dense(self.d_model, name="out_proj", dtype=self.dtype)(x)


class EncoderLayer(nn.Module):
    """Post-LN transformer encoder block.

    Parity: `CustomEncoderLayer` (`ray-tune-hpo-regression.py:122-178`):
    attention -> dropout -> residual -> LN, then FF (linear or depthwise-
    separable, `:148-155`) -> dropout -> residual -> LN, plus working
    stochastic depth on both residual branches.
    """

    d_model: int
    num_heads: int
    dim_feedforward: int
    dropout_rate: float = 0.1
    attention_type: str = "scaled_dot_product"
    key_dim_scaling: float = 0.5
    depthwise_separable_conv: bool = False
    attn_kernel_size: int = 3
    stochastic_depth_rate: float = 0.0
    # Feed-forward selector: "linear" | "depthwise_separable" | "moe".
    # None defers to the legacy `depthwise_separable_conv` bool (the
    # reference's knob, `ray-tune-hpo-regression.py:148-155`).
    feedforward_type: Optional[str] = None
    num_experts: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_coef: float = 1e-2
    seq_axis: Optional[str] = None
    seq_parallel_mode: str = "ring"
    batch_axis: Optional[str] = "dp"
    head_axis: Optional[str] = "tp"
    mesh: Optional[Mesh] = None
    # Compute dtype for the whole block (params stay float32). LayerNorm
    # gets it too: its scale/offset params are f32, statistics are computed
    # through flax's f32 promotion internally, and the output lands back in
    # this dtype so the residual stream stays narrow.
    dtype: Optional[jnp.dtype] = None
    rope: bool = False
    num_kv_heads: Optional[int] = None
    # Attention tile override (flash block_q/block_k, blockwise block) —
    # None = the kernel's measured-fastest defaults.
    block_size: Optional[int] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        attn = MultiHeadAttention(
            d_model=self.d_model,
            num_heads=self.num_heads,
            attention_type=self.attention_type,
            key_dim_scaling=self.key_dim_scaling,
            dropout_rate=self.dropout_rate,
            seq_axis=self.seq_axis,
            seq_parallel_mode=self.seq_parallel_mode,
            batch_axis=self.batch_axis,
            head_axis=self.head_axis,
            mesh=self.mesh,
            dtype=self.dtype,
            rope=self.rope,
            num_kv_heads=self.num_kv_heads,
            block_size=self.block_size,
            name="attention",
        )(x, deterministic=deterministic)
        attn = StochasticDepth(self.stochastic_depth_rate)(attn, deterministic)
        x = nn.LayerNorm(name="norm1", dtype=self.dtype)(x + attn)
        # Residual-stream pin: batch over dp (seq over sp when used),
        # d_model replicated — the Megatron layout the TP rules assume.
        x = constrain_activation(
            x, self.mesh, self.batch_axis, self.seq_axis, None
        )

        ff_type = self.feedforward_type or (
            "depthwise_separable" if self.depthwise_separable_conv else "linear"
        )
        if ff_type == "depthwise_separable":
            ff = DepthwiseSeparableFF(
                d_model=self.d_model,
                dim_feedforward=self.dim_feedforward,
                kernel_size=self.attn_kernel_size,
                dtype=self.dtype,
                name="ff",
            )(x)
        elif ff_type == "moe":
            # MoEFF follows its input's dtype (router pinned f32 inside).
            ff = MoEFF(
                d_model=self.d_model,
                dim_feedforward=self.dim_feedforward,
                num_experts=self.num_experts,
                top_k=self.expert_top_k,
                capacity_factor=self.capacity_factor,
                aux_loss_coef=self.moe_aux_coef,
                name="ff",
            )(x)
        elif ff_type == "linear":
            ff = LinearFF(
                d_model=self.d_model, dim_feedforward=self.dim_feedforward,
                dtype=self.dtype, name="ff"
            )(x)
        else:
            raise ValueError(
                f"Unknown feedforward_type {ff_type!r}; expected "
                f"'linear', 'depthwise_separable', or 'moe'"
            )
        ff = nn.Dropout(self.dropout_rate)(ff, deterministic=deterministic)
        ff = StochasticDepth(self.stochastic_depth_rate)(ff, deterministic)
        out = nn.LayerNorm(name="norm2", dtype=self.dtype)(x + ff)
        return constrain_activation(
            out, self.mesh, self.batch_axis, self.seq_axis, None
        )

"""Pipeline parallelism: GPipe-style microbatch pipelining over a ``pp`` axis.

Beyond-parity capability (the reference trains single-device models only —
SURVEY.md §2c): layer stages are sharded over the ``pp`` mesh axis and
microbatches stream through them, so a model deeper than one chip's HBM
trains with every stage busy once the pipeline fills.

Design, TPU-first:

* The schedule is data-flow, not control-flow: one ``lax.scan`` over
  ``M + P - 1`` ticks, where at tick ``t`` stage ``s`` processes microbatch
  ``t - s`` (a bubble of ``P - 1`` ticks at each end — GPipe).  All stages
  execute every tick under SPMD; out-of-range ticks compute on don't-care
  data and their results are masked out.  No data-dependent Python control
  flow — the whole pipeline is one XLA program.
* Activations hop stage-to-stage with ``jax.lax.ppermute`` — one
  nearest-neighbor ICI transfer per tick, the same primitive (and torus
  layout) ring attention rides.
* Stage parameters are ONE stacked pytree: leaves have leading dim
  ``num_stages``, sharded ``P("pp")`` (`stage_param_shardings`), so each
  device holds only its stage's slice.  Stage bodies see the slice with the
  leading dim dropped.
* Differentiable end to end: ``ppermute`` and ``scan`` have transpose
  rules, so ``jax.grad`` through ``pipeline_apply`` yields the standard
  GPipe backward schedule (reverse bubble) with no extra machinery.

``pipeline_apply`` is the generic engine; ``make_stacked_stage_fn`` adapts a
flax layer module into a stage body that scans its share of a stacked-layer
parameter tree (the nn.scan layout the shared-weights transformer already
uses), which is how a transformer encoder stack pipelines.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _pipeline_local(
    stage_params: Any,
    x_mb: jnp.ndarray,
    *,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    axis_name: str,
) -> jnp.ndarray:
    """Per-device body. ``stage_params`` leaves are [1, ...] (this stage's
    slice); ``x_mb`` is the local [M, mb/dp, ...] microbatch stack (only
    stage 0 reads it). Returns local [M, mb/dp, ...] outputs."""
    params = jax.tree_util.tree_map(lambda l: l[0], stage_params)
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]  # stage s -> s+1

    def tick(carry, t):
        prev_out, y_acc = carry
        # Activation arriving from the previous stage this tick.
        incoming = jax.lax.ppermute(prev_out, axis_name, fwd_perm)
        mb_idx = jnp.clip(t, 0, M - 1)
        x_in = jnp.where(stage == 0, x_mb[mb_idx], incoming)
        out = stage_fn(params, x_in)
        # The last stage finished microbatch t - (P - 1) this tick.
        widx = t - (n_stages - 1)
        valid = (widx >= 0) & (widx < M)
        y_new = jax.lax.dynamic_update_index_in_dim(
            y_acc, out, jnp.clip(widx, 0, M - 1), 0
        )
        y_acc = jnp.where(valid, y_new, y_acc)
        return (out, y_acc), None

    mb_shape = x_mb.shape[1:]
    out_shape = jax.eval_shape(
        stage_fn, params, jax.ShapeDtypeStruct(mb_shape, x_mb.dtype)
    )
    zero_out = jnp.zeros(out_shape.shape, out_shape.dtype)
    y0 = jnp.zeros((M,) + out_shape.shape, out_shape.dtype)
    (_, y), _ = jax.lax.scan(
        tick, (zero_out, y0), jnp.arange(M + n_stages - 1)
    )
    # Only the last stage holds real outputs; replicate them across 'pp'.
    y = jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y))
    return jax.lax.psum(y, axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: Optional[int] = None,
    batch_axis: Optional[str] = "dp",
) -> jnp.ndarray:
    """Run ``x`` through ``num_stages`` pipelined applications of ``stage_fn``.

    stage_params: pytree whose leaves have leading dim ``num_stages`` (the
    mesh's ``axis_name`` size), stacked in stage order and sharded over
    ``axis_name`` (see ``stage_param_shardings``).
    x: [B, ...] global batch; it is split into ``num_microbatches`` equal
    microbatches along dim 0 (M defaults to the stage count — the classic
    GPipe minimum for full utilization; more microbatches shrink the
    relative bubble).
    When the mesh also has ``batch_axis`` (dp), each microbatch's in-batch
    dim shards over it — dp x pp compose: dp rows pipeline disjoint batch
    slices instead of redundantly recomputing the same ones.
    Returns stage_fn^P(x) of shape [B, ...] — as if the stages ran
    sequentially on the whole batch.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.axis_names}")
    n_stages = mesh.shape[axis_name]
    M = int(num_microbatches or n_stages)
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(
            f"batch {B} not divisible by num_microbatches {M}"
        )
    baxis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    if baxis and (B // M) % mesh.shape[baxis] != 0:
        raise ValueError(
            f"microbatch size {B // M} not divisible by {baxis} axis size "
            f"{mesh.shape[baxis]}"
        )
    leaves = jax.tree_util.tree_leaves(stage_params)
    if leaves and leaves[0].shape[0] != n_stages:
        raise ValueError(
            f"stage_params leading dim {leaves[0].shape[0]} != pipeline "
            f"stages {n_stages} (mesh axis {axis_name!r})"
        )

    x_mb = x.reshape(M, B // M, *x.shape[1:])
    x_spec = P(None, baxis)
    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stage_params
    )
    fn = jax.shard_map(
        partial(_pipeline_local, stage_fn=stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    y = fn(stage_params, x_mb)
    return y.reshape(B, *y.shape[2:])


def stage_param_shardings(stage_params: Any, mesh: Mesh, axis_name: str = "pp"):
    """NamedShardings placing each stage's parameter slice on its device:
    leading (stage) dim over ``axis_name``, everything else replicated."""
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(
            mesh, P(axis_name, *([None] * (l.ndim - 1)))
        ),
        stage_params,
    )


def make_stacked_stage_fn(
    layer_apply: Callable[[Any, jnp.ndarray], jnp.ndarray],
) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
    """Adapt a single-layer apply into a stage body over stacked layers.

    ``layer_apply(layer_params, x) -> x`` is scanned over the stage's local
    stack of layer params (leaves [layers_per_stage, ...]) — so a pipeline
    of P stages x K layers each runs a P*K-layer network whose parameter
    tree is stacked once on the layer dimension, exactly the layout
    ``nn.scan``'s shared-weights transformer uses for its single shared
    layer (models/transformer.py).
    """

    def stage_fn(stage_stack, x):
        def body(h, layer_params):
            return layer_apply(layer_params, h), None

        out, _ = jax.lax.scan(body, x, stage_stack)
        return out

    return stage_fn

"""Ulysses-style sequence parallelism: all-to-all head/sequence reshuffle.

The second long-context strategy next to ring attention
(`parallel/ring_attention.py`), after the DeepSpeed-Ulysses pattern: instead
of rotating K/V chunks around a ring, ONE ``all_to_all`` per projection
trades the sequence sharding for a head sharding —

    [B, S/n, H, D]  --all_to_all-->  [B, S, H/n, D]

so every device computes *exact, unmodified* softmax attention over the FULL
sequence for its head group, then a second ``all_to_all`` restores the
sequence sharding for the rest of the (sequence-sharded) network.

Trade-offs vs the ring (why both exist):

* Ulysses moves activations twice per attention call but computes plain
  attention with no online-softmax bookkeeping — fewer, bigger MXU matmuls
  and a simpler backward; at moderate sequence lengths it is usually faster.
* Ring never materializes full-sequence activations (per-device memory
  O(S/n * S/n) per step) and its per-hop traffic is nearest-neighbor — it
  scales to sequences Ulysses cannot hold, since Ulysses stores full-S
  activations per head group (O(S * H/n * D) per device).
* Ulysses requires ``num_heads`` divisible by the sequence-axis size; the
  ring has no such constraint.

Both compose with dp (batch) and tp (head) sharding; select per layer with
``seq_parallel_mode`` (`models/layers.py`).

The reference has no sequence parallelism of any kind (SURVEY.md §5).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _ulysses_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool,
    scale: Optional[float],
    use_flash="auto",
    flash_interpret: bool = False,
) -> jnp.ndarray:
    """Per-device body; q, k, v are local [B, S/n, H_local, D] shards."""
    D = q.shape[-1]
    s = (D ** -0.5) if scale is None else scale

    # seq-sharded -> head-sharded: gather the full sequence, keep 1/n of the
    # local head group. One collective, all ICI.
    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)   # [B, S, H/n, D]
    S = qh.shape[1]

    from distributed_machine_learning_tpu.parallel.ring_attention import (
        _use_flash_inner,
    )

    if _use_flash_inner(use_flash, S, S, D):
        # After the reshuffle this is plain full-sequence attention — the
        # Pallas flash kernel (with its custom VJP) drops straight in; no
        # merge bookkeeping needed. Same measured-win gate as the ring.
        # Grouped kv (kh/vh at Hkv/n heads < qh's H/n) passes natively.
        from distributed_machine_learning_tpu.ops.pallas_attention import (
            flash_attention,
        )

        out = flash_attention(
            qh, kh, vh, scale=s, causal=causal, interpret=flash_interpret
        )
    else:
        if kh.shape[2] != qh.shape[2]:
            # Grouped kv rode the all_to_all at kv_heads (the comm saving);
            # the dense einsum needs full heads — a LOCAL repeat, no comm.
            g = qh.shape[2] // kh.shape[2]
            kh = jnp.repeat(kh, g, axis=2)
            vh = jnp.repeat(vh, g, axis=2)
        logits = jnp.einsum(
            "bqhd,bkhd->bqhk",
            qh.astype(jnp.float32) * s,
            kh.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if causal:
            cmask = jnp.tril(jnp.ones((S, S), bool))[None, :, None, :]
            logits = jnp.where(cmask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bqhk,bkhd->bqhd", p, vh.astype(jnp.float32))

    # head-sharded -> seq-sharded: the inverse reshuffle.
    return jax.lax.all_to_all(
        out.astype(q.dtype), axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def head_split(mesh: Mesh, axis_name: str, head_axis: Optional[str]) -> int:
    """The factor the all-to-alls split the head dim by (sp size x tp
    size). ONE definition — models/layers.py uses it to decide whether
    grouped kv can ride the reshuffle, so the rule cannot drift from the
    validation below."""
    t = (
        mesh.shape[head_axis]
        if head_axis and head_axis in mesh.axis_names
        else 1
    )
    n = mesh.shape[axis_name] if axis_name in mesh.axis_names else 1
    return n * t


def ulysses_attention(
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

    Same contract as ``ring_attention``: q, k, v are [B, S, H, D] global
    arrays with S divisible by the axis size; batch/heads optionally shard
    over ``batch_axis``/``head_axis``; returns [B, S, H, D] with the same
    sharding.  Additionally requires H divisible by (sequence-axis size x
    head-axis size), since the all_to_all re-shards heads.

    ``use_flash``: run the per-device full-sequence attention through the
    Pallas flash kernel ("auto" = the kernel's measured-win regime on TPU;
    see ``ring_attention``); ``flash_interpret`` for CPU tests.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.axis_names}")
    baxis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) else None
    haxis = head_axis if (head_axis and head_axis in mesh.axis_names) else None
    n = mesh.shape[axis_name]
    t = mesh.shape[haxis] if haxis else 1
    H = q.shape[2]
    if H % (n * t) != 0:
        raise ValueError(
            f"ulysses attention needs num_heads ({H}) divisible by "
            f"seq-axis size x head-axis size ({n}x{t}); use "
            f"seq_parallel_mode='ring' for head counts the all_to_all "
            f"cannot split"
        )
    Hkv = k.shape[2]
    if Hkv != H and (H % Hkv != 0 or Hkv % (n * t) != 0):
        raise ValueError(
            f"grouped kv ({Hkv} heads) must divide num_heads ({H}) and "
            f"divide by {n}x{t} to ride the all_to_all; broadcast kv to "
            f"full heads first (models/layers.py does this automatically)"
        )
    spec = P(baxis, axis_name, haxis, None)
    fn = jax.shard_map(
        partial(_ulysses_local, axis_name=axis_name, causal=causal,
                scale=scale, use_flash=use_flash,
                flash_interpret=flash_interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)

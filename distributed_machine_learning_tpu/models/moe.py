"""Mixture-of-Experts feed-forward with expert parallelism.

Beyond-parity capability (the reference has no MoE — SURVEY.md §2c lists
expert parallelism as absent): a sparsely-activated feed-forward block that
scales parameter count without scaling per-token FLOPs, designed the TPU way.

Design (GShard/Switch einsum formulation, the shape that maps onto the MXU
and GSPMD):

* Experts live as ONE stacked parameter tensor ``w_in [E, d_model, d_ff]`` /
  ``w_out [E, d_ff, d_model]``, sharded over the ``ep`` mesh axis
  (`parallel/sharding.py` rules).  There is no per-expert Python loop —
  expert compute is a single batched einsum over the E dimension, which XLA
  partitions across the mesh; token dispatch/combine einsums become
  all-to-all-style collectives on ICI automatically.
* Tokens are routed within fixed-size **groups** (GShard's trick): the
  dispatch/combine one-hot tensors are ``[G, group, E, capacity]`` with
  ``capacity ~ k*group/E``, so routing memory grows linearly with token
  count (``O(T * group * k)``) instead of quadratically — long sequences
  and big batches stay affordable.
* Routing math is dense and static-shaped under jit: top-k gating over
  router logits, position-in-expert via per-group cumulative sums, fixed
  per-group capacity.  Tokens over capacity are dropped (their FF
  contribution is zero; the encoder block's residual path still carries
  them) — the standard Switch trade for static shapes.
* The load-balance auxiliary loss (mean expert load x mean router prob,
  scaled by E, Switch-style) is sown into the ``"moe"`` collection already
  multiplied by ``aux_loss_coef``; the training loops add any sown values
  straight onto the objective (`tune/_regression_program.py`,
  `parallel/train_step.py`).
* Router math runs in float32 even under a bfloat16 compute dtype — gating
  is precision-sensitive, the rest of the block follows the input dtype.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp


def expert_capacity(capacity_factor: float, top_k: int, group: int,
                    num_experts: int) -> int:
    """Static per-expert slot count per routing group.

    Ceil of ``capacity_factor * top_k * group / num_experts`` (the
    GShard/Switch convention), floored at 1 slot so every expert is
    addressable even in degenerate tiny-group configs.
    """
    return max(math.ceil(capacity_factor * top_k * group / num_experts), 1)


# The collection an expert layer sows its routing counts into (never added
# to the objective, unlike ``"moe"``): ``local_pairs`` and
# ``load_max_over_mean`` a layer (models/hybrid_lm.py DroplessMoE); the
# token evaluation program reads it (tune/_regression_program.py).
STATS_COLLECTION = "moe_stats"


def collect_aux(mutated_collections) -> jnp.ndarray:
    """Sum every aux term sown into the ``"moe"`` collection of a
    ``model.apply(..., mutable=["moe"])`` result — THE way training loops
    fold the load-balance loss into their objective (keeps the two train
    paths, tune/_regression_program.py and parallel/train_step.py, in
    lockstep)."""
    leaves = jax.tree_util.tree_leaves(mutated_collections.get("moe", {}))
    if not leaves:
        return jnp.float32(0.0)
    return sum(jnp.sum(leaf) for leaf in leaves)


class MoEFF(nn.Module):
    """Top-k routed mixture-of-experts feed-forward (relu MLP experts)."""

    d_model: int
    dim_feedforward: int
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 1e-2
    # Routing-group size in tokens (GShard "G" dimension). Memory for the
    # dispatch tensors is T/group * group^2 * k — keep groups ~1k tokens.
    group_size: int = 1024

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.top_k > self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} > num_experts={self.num_experts}"
            )
        B, S, D = x.shape
        E, K = self.num_experts, self.top_k
        F = self.dim_feedforward
        T = B * S
        # Largest divisor of T at most group_size, so grouping is exact with
        # static shapes (same trick as blockwise attention's block size).
        g = min(self.group_size, T)
        while T % g:
            g -= 1
        G = T // g
        # Static per-expert capacity per group, with headroom for imbalance.
        capacity = expert_capacity(self.capacity_factor, K, g, E)

        # batch_axis=0: the expert dim is a batch of independent MLPs, not
        # receptive field — without it variance_scaling counts fan_in = E*D
        # and every expert starts sqrt(E) under-scaled.
        expert_init = nn.initializers.lecun_normal(batch_axis=0)
        w_in = self.param("w_in", expert_init, (E, D, F), jnp.float32)
        b_in = self.param("b_in", nn.initializers.zeros, (E, F), jnp.float32)
        w_out = self.param("w_out", expert_init, (E, F, D), jnp.float32)
        b_out = self.param("b_out", nn.initializers.zeros, (E, D), jnp.float32)

        toks = x.reshape(G, g, D)

        # -- routing (float32) ------------------------------------------------
        logits = nn.Dense(E, name="router", dtype=jnp.float32)(
            toks.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)                  # [G, g, E]
        gate_vals, expert_idx = jax.lax.top_k(probs, K)          # [G, g, K]
        gate_vals = gate_vals / (
            jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9
        )

        # Position-in-expert, slot by slot: slot j's tokens queue behind all
        # of slot j-1's tokens for the same expert (GShard ordering), within
        # each group independently.
        sel_onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [G,g,K,E]
        base = jnp.zeros((G, E), jnp.float32)
        dispatch = jnp.zeros((G, g, E, capacity), x.dtype)
        combine = jnp.zeros((G, g, E, capacity), x.dtype)
        for j in range(K):
            mask_j = sel_onehot[:, :, j, :]                       # [G, g, E]
            pos_j = jnp.cumsum(mask_j, axis=1) - 1.0 + base[:, None, :]
            keep_j = mask_j * (pos_j < capacity)
            pos_onehot = jax.nn.one_hot(
                jnp.where(keep_j > 0, pos_j, -1.0)
                .max(axis=-1)
                .astype(jnp.int32),
                capacity,
                dtype=jnp.float32,
            )                                                     # [G, g, C]
            disp_j = keep_j[..., None] * pos_onehot[:, :, None, :]  # [G,g,E,C]
            dispatch = dispatch + disp_j.astype(x.dtype)
            combine = combine + (
                disp_j * gate_vals[:, :, j, None, None]
            ).astype(x.dtype)
            base = base + mask_j.sum(axis=1)

        # -- expert compute (batched over G and E; ep-sharded under GSPMD) ----
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, toks)  # [G, E, C, D]
        h = nn.relu(
            jnp.einsum("gecd,edf->gecf", expert_in, w_in.astype(x.dtype))
            + b_in[None, :, None, :].astype(x.dtype)
        )
        expert_out = (
            jnp.einsum("gecf,efd->gecd", h, w_out.astype(x.dtype))
            + b_out[None, :, None, :].astype(x.dtype)
        )
        y = jnp.einsum("gtec,gecd->gtd", combine, expert_out)     # [G, g, D]

        # -- load-balance aux loss (Switch eq. 4): E * sum_e f_e * P_e --------
        top1 = jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32)
        load_frac = top1.mean(axis=(0, 1))   # fraction routed (top-1) per expert
        prob_frac = probs.mean(axis=(0, 1))  # mean router prob per expert
        aux = self.aux_loss_coef * E * jnp.sum(load_frac * prob_frac)
        self.sow("moe", "aux_loss", aux)

        return y.reshape(B, S, D)

"""Dropout PRNG implementation selection.

The reference's dropout randomness comes from cuDNN's hardware RNG
(`torch.nn.Dropout` inside the encoder stack, `ray-tune-hpo-regression.py:
148-177`) — fast, seeded, but not a counter-based stream.  JAX defaults to
threefry2x32, whose key derivation is expensive on TPU at HPO-sweep shapes:
on the bench workload (d_model 64, batch 32, seq 96) switching dropout
streams to the hardware RNG ("rbg") was recorded at ~1.5x sweep throughput
on a v5e chip (a figure from an earlier round, not measured on today's
code).

``rng_impl`` semantics in a trial config:

- unset / ``"auto"`` — hardware RNG on TPU (the measured win, and the
  reference-parity behavior), threefry elsewhere (CPU threefry is well
  optimized and keeps tests/bitstreams stable).
- ``"rbg"`` — hardware RNG everywhere it exists.
- ``"threefry"`` — force the JAX default (cross-platform reproducible
  streams, e.g. to compare a TPU run bit-for-bit against a CPU rerun).

All impls are deterministic in the seed; they differ in *which* streams a
seed produces, so changing impl changes trajectories (never validity).
"""

from __future__ import annotations

from typing import Any, Mapping, Optional


def resolve_rng_impl(config: Optional[Mapping[str, Any]]) -> Optional[str]:
    """Resolve a trial config's ``rng_impl`` to a ``jax.random.key`` impl.

    Returns ``None`` for the JAX default (threefry2x32) so the result can be
    passed straight to ``jax.random.key(seed, impl=...)`` /
    ``jax.random.wrap_key_data(data, impl=...)``.
    """
    val = (config or {}).get("rng_impl", "auto")
    if val in (None, "auto"):
        import jax

        return "rbg" if jax.default_backend() == "tpu" else None
    if val == "threefry":
        return None
    return str(val)

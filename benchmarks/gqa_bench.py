"""Microbenchmark: native grouped-query attention vs the repeat path.

Measures the flash Pallas kernel at num_kv_heads in {H, H/2, H/4, 1} two
ways per cell:

* ``native``  — k/v passed at kv_heads (the kernels stream the shared kv
  block per query head; dK/dV accumulate grouped in VMEM scratch);
* ``repeat``  — k/v ``jnp.repeat``-ed to full heads first (what the layer
  did before round 4: the repeated tensor is materialized in HBM, costing
  a write+read of (group-1)/group extra kv bytes plus the memory).

The delta is GQA's kernel-side kv-bandwidth/memory saving (VERDICT r3
next #4 asks for this measured on the chip). Prints one JSON line per
(seq, kv_heads, mode, direction) so runs are diffable.

Run on the TPU:      python benchmarks/gqa_bench.py
Run on CPU (smoke):  JAX_PLATFORMS=cpu python benchmarks/gqa_bench.py --seqs 256 --cells 2 --interpret
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# ONE timing harness for every microbench in this directory
# (attention_bench._sync), so it must not fork.
from attention_bench import _sync  # noqa: E402


def measure(fn, args, cells: int, steps: int) -> dict:
    _sync(fn(*args))  # compile outside the timer
    times = []
    for _ in range(cells):
        t0 = time.time()
        for _ in range(steps):
            out = fn(*args)
        _sync(out)
        times.append((time.time() - t0) / steps)
    times.sort()
    return {
        "ms": round(times[len(times) // 2] * 1e3, 3),
        "ms_spread": [round(times[0] * 1e3, 3), round(times[-1] * 1e3, 3)],
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", type=int, nargs="+", default=[2048, 4096])
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--cells", type=int, default=5)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--interpret", action="store_true",
                   help="Pallas interpreter (CPU smoke)")
    args = p.parse_args()

    from distributed_machine_learning_tpu.ops.pallas_attention import (
        flash_attention,
    )

    B, H, D = args.batch, args.heads, args.head_dim
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    dev = jax.devices()[0]
    print(f"# {dev.platform} {getattr(dev, 'device_kind', '?')} "
          f"B{B} H{H} D{D} {args.dtype}", file=sys.stderr)

    for S in args.seqs:
        rng = np.random.default_rng(0)
        # dmlint: disable=blocking-transfer-in-loop fresh shape per swept config (one staging per configuration, off the timed path)
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
        kv_counts = sorted({H, H // 2, H // 4, 1} - {0}, reverse=True)
        for Hkv in kv_counts:
            # dmlint: disable=blocking-transfer-in-loop fresh shape per swept config (off the timed path)
            k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
            # dmlint: disable=blocking-transfer-in-loop fresh shape per swept config (off the timed path)
            v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
            group = H // Hkv

            def native_fwd(q, k, v):
                return flash_attention(q, k, v, interpret=args.interpret)

            def repeat_fwd(q, k, v):
                kr = jnp.repeat(k, group, axis=2)
                vr = jnp.repeat(v, group, axis=2)
                return flash_attention(q, kr, vr, interpret=args.interpret)

            def grad_of(fwd):
                return jax.grad(
                    lambda q, k, v: jnp.sum(
                        fwd(q, k, v).astype(jnp.float32) ** 2
                    ),
                    argnums=(0, 1, 2),
                )

            modes = {"native": native_fwd}
            if group > 1:
                modes["repeat"] = repeat_fwd
            for mode, fwd in modes.items():
                fj = jax.jit(fwd)
                row = measure(fj, (q, k, v), args.cells, args.steps)
                print(json.dumps({
                    "seq": S, "kv_heads": Hkv, "mode": mode,
                    "direction": "fwd", **row,
                }), flush=True)
                gj = jax.jit(grad_of(fwd))
                row = measure(gj, (q, k, v), args.cells, args.steps)
                print(json.dumps({
                    "seq": S, "kv_heads": Hkv, "mode": mode,
                    "direction": "fwd+bwd", **row,
                }), flush=True)


if __name__ == "__main__":
    main()

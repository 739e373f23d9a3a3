"""Microbenchmark: attention kernels on one chip, across sequence lengths.

Compares the framework's attention implementations (`ops/attention.py`,
`ops/pallas_attention.py`) — XLA softmax ("scaled_dot_product"), the Pallas
flash kernel ("flash"), lax.scan blockwise, and O(n) linear attention — on
forward and forward+backward wall time. Prints one JSON line per
(kernel, seq_len, dtype) so regressions are diffable run to run.

Run on the TPU:      python benchmarks/attention_bench.py
Run on CPU (smoke):  JAX_PLATFORMS=cpu python benchmarks/attention_bench.py --seqs 128 256
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


def build(kernel: str, causal: bool):
    from distributed_machine_learning_tpu.ops.attention import (
        blockwise_attention,
        dot_product_attention,
        linear_attention,
    )

    if kernel == "xla_softmax":
        def fn(q, k, v):
            mask = None
            if causal:
                S = q.shape[1]
                mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
            return dot_product_attention(q, k, v, mask=mask)
        return fn
    if kernel == "flash_pallas":
        from distributed_machine_learning_tpu.ops.pallas_attention import (
            flash_attention,
        )

        return lambda q, k, v: flash_attention(q, k, v, causal=causal)
    if kernel == "blockwise":
        return lambda q, k, v: blockwise_attention(
            q, k, v, block_size=min(256, q.shape[1]), causal=causal
        )
    if kernel == "linear":
        return lambda q, k, v: linear_attention(q, k, v, causal=causal)
    raise ValueError(kernel)


def _sync(out):
    """Force completion with a device->host readback of one scalar.

    Fetching a value cannot complete before the computation has."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def timed(fn, *args, iters=10):
    _sync(fn(*args))  # compile outside the timer
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.time() - t0) / iters


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--seqs", type=int, nargs="+",
                   default=[512, 1024, 2048, 4096])
    p.add_argument("--dtypes", nargs="+", default=["float32", "bfloat16"])
    p.add_argument("--causal", action="store_true")
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args(argv)

    platform = jax.devices()[0].platform
    kernels = ["xla_softmax", "blockwise", "linear"]
    if platform == "tpu":
        kernels.insert(1, "flash_pallas")  # Mosaic compiles on TPU only

    for dtype_name in args.dtypes:
        dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32
        for S in args.seqs:
            rng = np.random.default_rng(0)
            mk = lambda: jnp.asarray(
                rng.normal(size=(args.batch, S, args.heads, args.head_dim)),
                dtype,
            )
            q, k, v = mk(), mk(), mk()
            for kernel in kernels:
                fn = build(kernel, args.causal)
                fwd = jax.jit(fn)

                def loss(q, k, v):
                    return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

                bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                try:
                    fwd_s = timed(fwd, q, k, v, iters=args.iters)
                    bwd_s = timed(bwd, q, k, v, iters=args.iters)
                except Exception as exc:  # noqa: BLE001 - record, keep going
                    print(json.dumps({
                        "kernel": kernel, "seq": S, "dtype": dtype_name,
                        "error": repr(exc)[:200],
                    }), flush=True)
                    continue
                # Softmax attention fwd FLOPs: 2 matmuls of 2*B*H*S^2*D;
                # causal does ~half (kernels skip fully-masked blocks).
                flops = 4.0 * args.batch * args.heads * S * S * args.head_dim
                if args.causal:
                    flops *= 0.5
                print(json.dumps({
                    "kernel": kernel, "seq": S, "dtype": dtype_name,
                    "platform": platform, "causal": args.causal,
                    "fwd_ms": round(fwd_s * 1e3, 3),
                    "fwd_bwd_ms": round(bwd_s * 1e3, 3),
                    "fwd_tflops": round(flops / fwd_s / 1e12, 2),
                }), flush=True)


if __name__ == "__main__":
    main()

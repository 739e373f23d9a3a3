"""Time the gated delta rule (value, and value with all five gradients) on
the chip at train_q3next_s8192's call ([2, 8192], 16 key heads serving 32
value heads of 128, bfloat16 products), the parent's XLA program against
this tree's kernels; with ``--all`` also the float32 inverse done otherwise:
16 x 16 blocks by the product form joined by products, panels of 8 rows,
diagonal blocks of 32 or 16 rows by substitution joined by products.

    python3 _archive/gdn_time.py <the parent's ops/gated_delta.py> [--all]
"""
import importlib.util
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_machine_learning_tpu.ops import gated_delta
from distributed_machine_learning_tpu.ops import pallas_gated_delta as pg

B, S, HK, HV, D = 2, 8192, 16, 32, 128
BF16 = jnp.bfloat16


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inverse_blocks(a, b=16):
    """16 x 16 diagonal blocks by the product form, joined by products."""
    n = a.shape[0]
    r, c = pg._grid(n)
    eye = (r == c).astype(jnp.float32)
    diag = jnp.where((r // b) == (c // b), a, 0.0)
    t, p, m = eye - diag, diag, 2
    while m < b:
        p = pg._f32(p, p, 1, 0)
        t = t + pg._f32(t, p, 1, 0)
        m *= 2
    low = pg._f32(t, a - diag, 1, 0)      # nilpotent: low^(n / b) = 0
    inv, p, m = eye - low, low, 2
    while m < n // b:
        p = pg._f32(p, p, 1, 0)
        inv = inv + pg._f32(inv, p, 1, 0)
        m *= 2
    return pg._f32(inv, t, 1, 0)


def inverse_joined(a, block):
    """Diagonal blocks of ``block`` rows by substitution, all at once, then
    joined pairwise by float32 products: the inverse of [[M, 0], [L, N]]
    is [[M', 0], [-N' L M', N']]."""
    n = a.shape[0]
    r, c = pg._grid(n)
    t = (r == c).astype(jnp.float32)
    for j in range(block - 1):
        pieces, done = [], 0
        for s in range(0, n, block):
            lo = s + (j + 1) // 8 * 8
            pieces += [t[done:lo], t[lo:s + block]
                       - a[lo:s + block, s + j:s + j + 1] * t[s + j:s + j + 1]]
            done = s + block
        t = jnp.concatenate([p for p in pieces if p.shape[0]], axis=0)
    size = block
    while size < n:
        lower = ((r // size) % 2 == 1) & (c // size == r // size - 1)
        t = t - pg._f32(t, pg._f32(jnp.where(lower, a, 0.0), t, 1, 0), 1, 0)
        size *= 2
    return t


def inverse_panels(a, p=8):
    """Panels of 8 rows: each panel by substitution among its own rows,
    then every later row takes off its panel part in one float32
    product."""
    n = a.shape[0]
    r, c = pg._grid(n)
    t = (r == c).astype(jnp.float32)
    for s in range(0, n, p):
        rows = t[s:s + p]
        for j in range(s, s + p - 1):
            lo = j + 1 - s
            low = rows[lo:] - a[j + 1:s + p, j:j + 1] * rows[j - s:j - s + 1]
            rows = jnp.concatenate([rows[:lo], low], axis=0)
        parts = [t[:s], rows] if s else [rows]
        if s + p < n:
            parts.append(t[s + p:] - pg._f32(a[s + p:, s:s + p], rows, 1, 0))
        t = jnp.concatenate(parts, axis=0)
    return t


def inputs(seed):
    ks = jax.random.split(jax.random.key(seed), 6)
    k = jax.random.normal(ks[1], (B, S, HK, D)) + 0.3
    return (
        jax.random.normal(ks[0], (B, S, HK, D)) * D ** -0.5 / 4,
        k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        jax.random.normal(ks[2], (B, S, HV, D)).astype(BF16),
        -jax.random.uniform(ks[3], (B, S, HV), maxval=0.3),
        jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, HV))),
        jax.random.normal(ks[5], (B, S, HV, D)),
    )


def timed(fn, args, n=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def programs(rule):
    def value(q, k, v, g, beta):
        return rule(q, k, v, g, beta)

    def grads(q, k, v, g, beta, probe):
        return jax.value_and_grad(
            lambda *a: jnp.sum(rule(*a) * probe), argnums=(0, 1, 2, 3, 4)
        )(q, k, v, g, beta)

    return jax.jit(value), jax.jit(grads)


def main():
    parent = load(sys.argv[1], "gated_delta_parent")
    print("device", jax.devices()[0].device_kind, flush=True)
    *args, probe = inputs(43)

    def parent_rule(q, k, v, g, beta):
        q, k = (jnp.repeat(a, HV // HK, axis=2) for a in (q, k))
        return parent.chunk_gated_delta_rule(q, k, v, g, beta,
                                             matmul_dtype=BF16)

    def kernel_rule(q, k, v, g, beta):
        return gated_delta.chunk_gated_delta_rule(q, k, v, g, beta,
                                                  matmul_dtype=BF16)

    results = {}
    inverse = pg._inverse
    # (name, rule, the inverse of one system)
    variants = [("parent_xla", parent_rule, inverse),
                ("kernel", kernel_rule, inverse)]
    if "--all" in sys.argv:  # the other inverses timed
        variants += [
            ("kernel_products16", kernel_rule, inverse_blocks),
            ("kernel_panels8", kernel_rule, inverse_panels),
            ("kernel_joined32", kernel_rule, lambda a: inverse_joined(a, 32)),
            ("kernel_joined16", kernel_rule, lambda a: inverse_joined(a, 16)),
        ]
    for name, rule, this_inverse in variants:
        pg._inverse = this_inverse
        value, grads = programs(rule)
        t0 = time.perf_counter()
        jax.block_until_ready(value(*args))
        jax.block_until_ready(grads(*args, probe))
        compile_s = time.perf_counter() - t0
        fwd_ms, o = timed(value, args)
        grad_ms, (_, g) = timed(grads, (*args, probe))
        results[name] = (o, g)
        line = (f"{name}: forward {fwd_ms:.3f} ms, value and grads "
                f"{grad_ms:.3f} ms (first calls {compile_s:.1f} s)")
        if name != "parent_xla":
            o0, g0 = results["parent_xla"]
            diffs = [float(jnp.max(jnp.abs(o.astype(jnp.float32) - o0)))] + [
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(g, g0)
            ]
            scale = [float(jnp.max(jnp.abs(o0)))] + [
                float(jnp.max(jnp.abs(b.astype(jnp.float32)))) for b in g0
            ]
            line += "; |diff| to parent (o q k v g beta): " + " ".join(
                f"{d:.2e}/{s:.2e}" for d, s in zip(diffs, scale))
        print(line, flush=True)
    pg._inverse = inverse


if __name__ == "__main__":
    main()

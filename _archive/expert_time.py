"""Time routed_experts (value and all five gradients) on the chip at the
three LM cells' shapes, for the parent's module and this tree's at stride
multiples 2, 4 and 8.  python3 _archive/expert_time.py <parent tree>"""
import importlib.util
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MOD = "distributed_machine_learning_tpu/ops/grouped_experts.py"
parent = load(os.path.join(sys.argv[1], MOD), "ge_parent")
change = load(MOD, "ge_change")
print("device", jax.devices()[0].device_kind, flush=True)

# name: (d, f, held, K, activation, routing)
CELLS = {
    "zaya1_collapsed": (2048, 2048, 8, 1, "silu", "one"),
    "zaya1_uniform": (2048, 2048, 8, 1, "silu", "uniform16"),
    "smallthinker_collapsed": (2560, 768, 16, 6, "relu", "first_on_one64"),
    "q3next_uniform": (2048, 512, 32, 10, "silu", "uniform512"),
}
T = 16384


def routing(kind, K, rng):
    if kind == "one":
        return np.full((T, K), 3)
    if kind == "uniform16":
        return rng.integers(0, 16, size=(T, K))
    if kind == "first_on_one64":
        rest = np.stack([rng.permutation(np.delete(np.arange(64), 5))[:K - 1]
                         for _ in range(T)])
        return np.concatenate([np.full((T, 1), 5), rest], 1)
    if kind == "uniform512":
        return np.stack([rng.permutation(512)[:K] for _ in range(T)])


def timed(fn, args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


for name, (d, f, held, K, act, kind) in CELLS.items():
    rng = np.random.default_rng(0)
    top_e = jnp.asarray(routing(kind, K, rng), jnp.int32)
    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
    w = jax.nn.softmax(jax.random.normal(ks[1], (T, K)), -1)
    wg = jax.random.normal(ks[2], (held, d, f)) * 0.02
    wu = jax.random.normal(ks[3], (held, d, f)) * 0.02
    wd = jax.random.normal(ks[4], (held, f, d)) * 0.02
    dy = jax.random.normal(ks[5], (T, d))
    args = (x, w, wg, wu, wd)
    results = {}
    for label, mod, n in (("parent", parent, None), ("s2", change, 2),
                          ("s4", change, 4), ("s8", change, 8)):
        if n is not None:
            mod.STRIDE_TILES = n

        def loss(x, w, wg, wu, wd, mod=mod):
            plan = mod.make_plan(top_e, 0, held, 256)
            return jnp.sum(dy * mod.routed_experts(x, w, wg, wu, wd, plan, 256, act))

        def fwd(x, w, wg, wu, wd, mod=mod):
            plan = mod.make_plan(top_e, 0, held, 256)
            return mod.routed_experts(x, w, wg, wu, wd, plan, 256, act)

        f_ms, y = timed(jax.jit(fwd), args)
        g_ms, grads = timed(jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))), args)
        results[label] = (y, grads)
        extra = ""
        if label != "parent":
            py, (pl, pg) = results["parent"][0], results["parent"][1]
            gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                        / max(float(jnp.max(jnp.abs(b.astype(jnp.float32)))), 1e-30))
                    for a, b in zip(grads[1], pg)]
            extra = (f" y_gap {float(jnp.max(jnp.abs(y - py)) / jnp.max(jnp.abs(py))):.2e}"
                     f" grad_gaps {[f'{g:.1e}' for g in gaps]}")
        share = ""
        if hasattr(mod, "stride_row_share"):
            share = f" share {float(mod.stride_row_share(mod.make_plan(top_e, 0, held, 256))):.3f}"
        print(f"[time] {name} {label}: fwd {f_ms:.3f} ms, fwd+bwd {g_ms:.3f} ms{share}{extra}",
              flush=True)

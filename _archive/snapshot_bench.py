"""How long a device-side snapshot of a trial's state takes by each road
(PR 35).  Run by hand on the chip, imported by nothing:

    chiprun -- env PYTHONPATH=. python3 _archive/snapshot_bench.py

A float32 tree the size of the regressor cells' checkpoint (16 layers of
d_model 512 under adam: 809 device leaves and 2 Python ones, 0.6 GB) is
copied on the device three ways, each six times after a first call that
is reported by itself (the jitted road's first call traces and compiles;
the persistent compile cache is off, so that compile is cold):

* ``leaf``: ``[x.copy() for x in leaves]``, one eager dispatch a leaf;
* ``jit``: one compiled program over the list that returns a copy of each;
* ``put``: one ``jax.device_put(leaves, may_alias=False)``.

A line a road: host seconds until the call returns, seconds until every
copy is ready, the allocator's bytes in use before and with the snapshot
alive, its peak, and whether every copy's buffer is another than its
original's.  Then where the jitted road's host time lies: the same 809
arguments into one result, one argument into 809 results, and the copies
stacked into one result a shape.  A last line times
``AsyncCheckpointWriter.submit`` of the working tree on the same state,
whole saves and all.
"""

import json
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

REPS = 6


def make_state(seed: int = 0):
    """params + adam's two moments of a 16-layer, d_model 512 regressor."""
    d, ff, layers = 512, 2048, 16
    shapes = {"in/kernel": (16, d), "in/bias": (d,), "pos": (2048, d),
              "out_norm/scale": (d,), "out_norm/bias": (d,),
              "head/kernel": (d, 1), "head/bias": (1,)}
    for i in range(6):
        shapes[f"extra{i}"] = (d,)
    for layer in range(layers):
        for name in "qkvo":
            shapes[f"l{layer}/{name}/kernel"] = (d, d)
            shapes[f"l{layer}/{name}/bias"] = (d,)
        shapes[f"l{layer}/ff1/kernel"] = (d, ff)
        shapes[f"l{layer}/ff1/bias"] = (ff,)
        shapes[f"l{layer}/ff2/kernel"] = (ff, d)
        shapes[f"l{layer}/ff2/bias"] = (d,)
        for norm in ("norm1", "norm2"):
            shapes[f"l{layer}/{norm}/scale"] = (d,)
            shapes[f"l{layer}/{norm}/bias"] = (d,)
    key = jax.random.PRNGKey(seed)
    make = jax.jit(
        lambda k: {n: jax.random.normal(jax.random.fold_in(k, i), s)
                   for i, (n, s) in enumerate(shapes.items())}
    )
    state = {"params": make(key),
             "opt_state": {"mu": make(jax.random.fold_in(key, 1)),
                           "nu": make(jax.random.fold_in(key, 2)),
                           "count": jnp.zeros((), jnp.int32),
                           "schedule_count": jnp.zeros((), jnp.int32)},
             "epoch": 1, "rng_impl": "rbg"}
    jax.block_until_ready(state)
    return state


def copy_leaf_by_leaf(leaves):
    return [x.copy() for x in leaves]


@jax.jit
def copy_jitted(leaves):
    return [jnp.copy(x) for x in leaves]


def copy_put(leaves):
    return jax.device_put(leaves, may_alias=False)


@jax.jit
def many_in_one_out(leaves):
    return sum(x.reshape(-1)[0].astype(jnp.float32) for x in leaves)


@jax.jit
def one_in_many_out(flat):
    return [flat[i * 1024:(i + 1) * 1024] + 1 for i in range(809)]


@jax.jit
def copy_stacked_by_shape(leaves):
    by_shape = {}
    for x in leaves:
        by_shape.setdefault((x.shape, x.dtype), []).append(x)
    return [jnp.stack(xs) for xs in by_shape.values()]


def anatomy(name, fn, arg):
    def once():
        t0 = time.perf_counter()
        out = fn(arg)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        return t1 - t0, time.perf_counter() - t0

    first = once()
    runs = [once() for _ in range(REPS)]
    print(json.dumps({
        "anatomy": name, "first_call_s": round(first[1], 4),
        "results": len(jax.tree.leaves(fn(arg))),
        "return_ms": [round(r[0] * 1e3, 2) for r in runs],
        "ready_ms": [round(r[1] * 1e3, 2) for r in runs],
    }), flush=True)


def in_use(device):
    stats = device.memory_stats() or {}
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


def read(name, road, leaves, device):
    def once():
        before, _ = in_use(device)
        t0 = time.perf_counter()
        out = road(leaves)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        with_copy, peak = in_use(device)
        fresh = all(
            a.unsafe_buffer_pointer() != b.unsafe_buffer_pointer()
            and a.sharding == b.sharding
            for a, b in zip(out, leaves)
        )
        return {"return_s": t1 - t0, "ready_s": t2 - t0, "before": before,
                "with_copy": with_copy, "peak": peak, "fresh": fresh}

    first = once()
    runs = [once() for _ in range(REPS)]
    line = {
        "road": name,
        "first_call_s": round(first["ready_s"], 4),
        "return_ms": [round(r["return_s"] * 1e3, 2) for r in runs],
        "ready_ms": [round(r["ready_s"] * 1e3, 2) for r in runs],
        "return_ms_median": round(
            statistics.median(r["return_s"] for r in runs) * 1e3, 2),
        "bytes_before": runs[-1]["before"],
        "bytes_with_copy": runs[-1]["with_copy"],
        "peak_bytes": runs[-1]["peak"],
        "fresh_buffers_same_sharding": all(r["fresh"] for r in [first] + runs),
    }
    print(json.dumps(line), flush=True)


def read_submit(state, device):
    """The working tree's ``submit``, a whole save after each."""
    from distributed_machine_learning_tpu.tune.checkpoint import (
        AsyncCheckpointWriter,
    )

    writer = AsyncCheckpointWriter()
    returns, waits = [], []
    with tempfile.TemporaryDirectory() as tmp:
        for i in range(REPS + 1):
            t0 = time.perf_counter()
            path = writer.submit(f"{tmp}/ckpt_{i:06d}.msgpack", state)
            t1 = time.perf_counter()
            writer.wait(path)
            returns.append(t1 - t0)
            waits.append(time.perf_counter() - t1)
            # As in a cell, where a write ends mid-epoch: the writer's
            # thread has let its snapshot go before the next report.
            time.sleep(0.5)
        writer.close()
    print(json.dumps({
        "road": "AsyncCheckpointWriter.submit",
        "first_call_s": round(returns[0], 4),
        "return_ms": [round(s * 1e3, 2) for s in returns[1:]],
        "write_s": [round(s, 3) for s in waits[1:]],
        "peak_bytes": in_use(device)[1],
    }), flush=True)


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    device = jax.devices()[0]
    state = make_state()
    leaves = [x for x in jax.tree.leaves(state) if isinstance(x, jax.Array)]
    print(json.dumps({
        "device": device.device_kind, "platform": device.platform,
        "jax": jax.__version__, "leaves": len(jax.tree.leaves(state)),
        "device_leaves": len(leaves),
        "bytes": int(sum(x.nbytes for x in leaves)),
        "bytes_in_use": in_use(device)[0],
    }), flush=True)
    for name, road in (("leaf", copy_leaf_by_leaf), ("jit", copy_jitted),
                       ("put", copy_put), ("leaf_again", copy_leaf_by_leaf)):
        read(name, road, leaves, device)
    anatomy("809 arguments, 1 result", many_in_one_out, leaves)
    anatomy("1 argument, 809 results", one_in_many_out,
            jnp.zeros(809 * 1024, jnp.float32))
    anatomy("809 arguments, copies stacked by shape", copy_stacked_by_shape,
            leaves)
    read_submit(state, device)


if __name__ == "__main__":
    main()

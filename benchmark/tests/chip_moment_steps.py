"""Step by step, how far Adam's first moment of the plain reference with
bfloat16 operands lies from the float32 reference's, at a train cell's own
size.  Run through the chip tool:

    python3 benchmark/tests/chip_moment_steps.py train_s512 <data-seed> <seed> [<seed> ...]

The look behind ``grad_moment_gap_med`` being printed and not compared
(PERF.md, section 2): a second implementation at the program's precision,
and the one place where the moment after one step, the first gradient as
the optimizer gets it, can be read, since the program's unit is the epoch.
Every seed trains on the data of ``<data-seed>``.  One line a seed and a
step: the moment's ``gap_med`` and ``diff_med`` and the change's
``gap_med``.  Not collected by pytest: it needs the chip.
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())
import jax
import jax.numpy as jnp

from benchmark.drivers import train as drv
from benchmark.reference import regressor as ref
from benchmark.run import Cell, Run

cell = Cell.load(os.getcwd(), sys.argv[1])
data_seed, seeds = int(sys.argv[2]), [int(s) for s in sys.argv[3:]]
t = cell.traffic
batch, steps = int(t["batch_size"]), int(t["steps_per_epoch"])
programs = {}
for seed in seeds:
    run = Run(cell=cell, seed=seed, seconds=0.0, traced=False,
              devices=jax.devices()[:1], peaks=None, work_dir="")
    cfg = drv.trial_config(run)
    train, _ = drv.make_data(run, data_seed)
    x, y = jnp.asarray(train.x, jnp.float32), jnp.asarray(train.y, jnp.float32)
    params0 = ref.init_params(
        cfg, jax.random.key(ref.program_seed(seed, "init")),
        int(cell.config["features"]),
    )
    for quant in (None, ref.bf16):
        if quant not in programs:
            programs[quant] = drv._reference_programs(run, cfg, quant, None)[0]
    perm_key, key = jax.random.split(jax.random.key(
        ref.program_seed(seed, "epoch", 0), impl=ref.program_rng_impl(cfg)
    ))
    perm = jax.random.permutation(perm_key, len(train))[: steps * batch]
    perm = perm.reshape(steps, batch)
    sides = {q: (params0, ref.adam_init(params0)) for q in programs}
    for i in range(steps):
        key, dkey = jax.random.split(key)
        leaves = {}
        for q, step in programs.items():
            params, opt, loss = step(
                *sides[q], x[perm[i]], y[perm[i]], dkey,
                float(cfg["learning_rate"]), float(cfg.get("weight_decay", 0.0)),
            )
            sides[q] = (params, opt)
            leaves[q] = (
                drv._leaves(opt["mu"]),
                drv._leaves(jax.tree.map(lambda a, b: a - b, params, params0)),
                float(loss),
            )
        (mu_w, dp_w, loss_w), (mu_g, dp_g, loss_g) = leaves[None], leaves[ref.bf16]
        moment = drv.leaf_rows(mu_g, mu_w)
        median = sorted(r[0] for r in moment.values())[len(moment) // 2]
        keep = [k for k, r in moment.items() if r[0] >= 1e-3 * median]
        m = drv.leaf_gaps(moment, keep)
        c = drv.leaf_gaps(drv.leaf_rows(dp_g, dp_w), keep)
        print(json.dumps({
            "seed": seed, "step": i + 1, "loss_f32": loss_w, "loss_bf16": loss_g,
            "grad_moment_gap_med": m["gap_med"], "grad_moment_diff_med": m["diff_med"],
            "grad_moment_median_leaf": median,
            "param_change_gap_med": c["gap_med"], "param_change_diff_med": c["diff_med"],
        }), flush=True)

"""One-off: flagship MFU vs batch size on the real chip.

The driver-artifact flagship (bench.py FLAGSHIP) measured MFU 0.243 at
batch 8 (2026-07-31 capture).  The MXU wants a bigger M dimension; this
sweeps batch {8, 16, 32} at the same shape to find the best-MFU config
before promoting it to FLAGSHIP.  Run from the repo root on a machine
with a TPU; one process at a time may hold the chip.
"""

import functools
import json
import sys
import time

sys.path.insert(0, ".")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import _median  # same timing statistic as FLAGSHIP's capture
    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
        train_step_flops,
    )

    S, F = 2048, 16
    cfg = {
        "model": "transformer", "d_model": 512, "num_heads": 8,
        "num_layers": 4, "dim_feedforward": 2048, "dropout": 0.0,
        "attention_type": "flash", "compute_dtype": "bfloat16",
        "max_seq_length": S,
    }
    peak = device_peak_flops(jax.devices()[0], compute_dtype="bfloat16")
    for B in (8, 16, 32):
        model = build_model(dict(cfg))
        rng = jax.random.PRNGKey(0)
        # dmlint: disable=blocking-transfer-in-loop fresh shape per swept batch size (one staging per config, off the timed path)
        x = jnp.asarray(np.random.RandomState(0).randn(B, S, F), jnp.float32)
        # dmlint: disable=blocking-transfer-in-loop fresh shape per swept batch size (off the timed path)
        y = jnp.asarray(np.random.RandomState(1).randn(B, 1), jnp.float32)
        params = model.init({"params": rng, "dropout": rng}, x,
                            deterministic=True)["params"]
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, x, y):
            def loss_of(p):
                preds = model.apply({"params": p}, x, deterministic=True)
                return jnp.mean((preds.astype(jnp.float32) - y) ** 2)
            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state2 = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state2, loss

        t0 = time.time()
        params, opt_state, loss = step(params, opt_state, x, y)
        float(loss)
        compile_s = time.time() - t0
        cells = []
        for _ in range(6):
            t0 = time.time()
            for _ in range(5):
                params, opt_state, loss = step(params, opt_state, x, y)
            float(loss)
            cells.append((time.time() - t0) / 5)
        step_s = _median(cells)
        cells.sort()
        flops = train_step_flops(cfg, B, S, F)
        print(json.dumps({
            "batch": B, "step_s": round(step_s, 5),
            "spread": [round(cells[0], 5), round(cells[-1], 5)],
            "compile_s": round(compile_s, 1),
            "mfu": round(flops / step_s / peak, 4) if peak else None,
            "tflops": round(flops / step_s / 1e12, 2),
        }), flush=True)


if __name__ == "__main__":
    main()

"""The hybrid language model against its plain reference on the TPU, at a
size that compiles in seconds.  Run through the chip tool:
``python3 benchmark/tests/chip_lm_small.py``.

Published head sizes (the causal flash kernels at D 256 with 4 : 2 grouped
heads, the chunked rule at 128 x 128 states), narrow everything else; the
program in bfloat16 as the cell runs it, the reference in float32 at
``highest``.  Prints the largest gap of the logits against their spread,
the loss gap, and every gradient leaf's norm gap, then the same with both
sides in float32 (what is left then is the order of the sums and the
kernels' own bf16 passes).  Then, at the cell's own widths, the program's
initialisation against the reference's ``init_params`` on three seeds
(``init_gap``, 0 where every leaf is equal to the bit), and what
``AsyncCheckpointWriter._device_has_room_for`` costs a report on a tree of
the old train cells' 811 leaves.  Not collected by pytest: it needs the chip.
"""
import json
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import train_lm
from benchmark.reference import gated_hybrid_lm as ref
from distributed_machine_learning_tpu.models import build_model
from distributed_machine_learning_tpu.ops.losses import get_loss

REF_CFG = {
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "hidden_size": 256, "vocab_size": 2048, "rms_norm_eps": 1e-6,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 256,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
    "published": {"num_experts": 32}, "held_experts": [8, 8],
}
TRIAL = {
    "model": "gated_hybrid_lm", "vocab_size": 2048, "num_layers": 4,
    "d_model": 256, "full_attention_interval": 4, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 256, "rotary_dim": 64, "rope_theta": 1e7,
    "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "conv_width": 4, "num_experts": 32, "top_k": 4, "expert_width": 128,
    "shared_width": 128, "held_experts": [8, 8],
}


def main():
    tokens = jax.random.randint(jax.random.key(0), (2, 1024), 0, 2048)
    targets = jax.random.randint(jax.random.key(1), (2, 1024), 0, 2048)
    for dtype in ("bfloat16", "float32"):
        model = build_model(dict(TRIAL, compute_dtype=dtype))
        params = jax.jit(model.init)(
            {"params": jax.random.key(2)}, tokens
        )["params"]

        def program(p):
            logits = model.apply({"params": p}, tokens)
            return get_loss("cross_entropy")(logits, targets), logits

        def reference(p):
            logits = ref.forward(p, tokens, REF_CFG)
            return jnp.mean(
                ref.token_losses(p, tokens, targets, REF_CFG)
            ), logits

        (loss, logits), grads = jax.jit(
            jax.value_and_grad(program, has_aux=True)
        )(params)
        (want_loss, want), want_grads = jax.jit(
            jax.value_and_grad(reference, has_aux=True)
        )(train_lm.to_reference(params, REF_CFG))
        grads = train_lm.to_reference(grads, REF_CFG)
        gaps = {}
        for name, w in want_grads.items():
            w = np.asarray(w, np.float64)
            g = np.asarray(grads[name], np.float64).reshape(w.shape)
            gaps[name] = float(
                np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
            )
        worst = sorted(gaps.items(), key=lambda kv: -kv[1])[:5]
        print("LM_SMALL", dtype, json.dumps({
            "logit_gap_max_over_std": float(
                jnp.max(jnp.abs(logits.astype(jnp.float32) - want))
                / jnp.std(want)
            ),
            "loss": float(loss), "reference_loss": float(want_loss),
            "grad_diff_over_norm_median": float(np.median(list(gaps.values()))),
            "grad_diff_over_norm_worst": worst,
        }), flush=True)


def init_at_the_cells_size(seeds=(2147483777, 2147484207, 3000000019)):
    from benchmark.run import load_json

    model_cfg = load_json("benchmark", "configs", "qwen3-next-80b-a3b-ep16.json")
    for seed in seeds:
        cfg = dict(model_cfg["trial"], seed=seed)
        t0 = time.time()
        want = train_lm.reference_init(cfg, model_cfg)
        t1 = time.time()
        got = train_lm.program_init(cfg, model_cfg, np.zeros((1, 64), np.int32))
        unequal = [k for k in want if not np.array_equal(got[k], want[k])]
        print("LM_INIT", json.dumps({
            "seed": seed, "init_gap": train_lm.init_gap(got, want),
            "leaves": len(want), "unequal_leaves": unequal,
            "parameters": int(sum(v.size for v in want.values())),
            "reference_s": round(t1 - t0, 1),
            "program_s": round(time.time() - t1, 1),
        }), flush=True)
        del got, want


def room_check_cost(leaves=811, calls=200):
    from distributed_machine_learning_tpu.tune.checkpoint import (
        AsyncCheckpointWriter,
    )

    tree = [jnp.full((512, 360), float(i), jnp.float32) for i in range(leaves)]
    jax.block_until_ready(tree)
    room = AsyncCheckpointWriter._device_has_room_for
    room(tree)
    t0 = time.perf_counter()
    for _ in range(calls):
        verdict = room(tree)
    each = (time.perf_counter() - t0) / calls
    t0 = time.perf_counter()
    for _ in range(calls):
        jax.devices()[0].memory_stats()
    stats = (time.perf_counter() - t0) / calls
    print("ROOM_CHECK", json.dumps({
        "leaves": leaves, "tree_bytes": int(sum(x.nbytes for x in tree)),
        "verdict": bool(verdict), "ms_a_call": round(each * 1e3, 4),
        "memory_stats_ms": round(stats * 1e3, 4),
    }), flush=True)


if __name__ == "__main__":
    main()
    init_at_the_cells_size()
    room_check_cost()

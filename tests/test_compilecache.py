"""compilecache/: program keys, AOT executables, counters, and the
counter-verified compile-once acceptance (ISSUE 5).

The decisive property: the SECOND occurrence of any (shape class, batch
shape, dtype, donation signature) program is free — in this process (jit
cache), in a fresh process (persistent + AOT tiers, asserted by counters,
not eyeballed), and across workers (origin tests in
test_compile_origin.py).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_machine_learning_tpu import compilecache as cc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# program keys
# ---------------------------------------------------------------------------

BASE_CFG = {
    "model": "transformer", "d_model": 64, "num_heads": 4, "num_layers": 2,
    "batch_size": 32, "num_epochs": 5, "learning_rate": 0.01,
    "weight_decay": 1e-4, "seed": 7, "lr_schedule": "constant",
    "hidden_sizes": (16, 8),
}


def test_key_ignores_non_structural_hparams():
    """lr / weight_decay / seed ride in optimizer state and PRNG args —
    configs differing ONLY there trace identical HLO and must share a key."""
    k0 = cc.program_key(BASE_CFG)
    assert k0 == cc.program_key(
        dict(BASE_CFG, learning_rate=3.3, weight_decay=0.0, seed=999)
    )


@pytest.mark.parametrize("change", [
    {"d_model": 128},
    {"num_heads": 8},
    {"num_layers": 3},
    {"batch_size": 64},
    {"num_epochs": 6},          # scan trip counts shape the program
    {"hidden_sizes": (32,)},
    {"model": "mlp"},
    {"optimizer": "lamb"},      # optimizer family = chain structure
    {"compute_dtype": "bfloat16"},
])
def test_key_splits_on_shape_bearing_hparams(change):
    assert cc.program_key(BASE_CFG) != cc.program_key(dict(BASE_CFG, **change))


def test_key_splits_on_batch_shape_dtype_donation():
    k = cc.program_key(BASE_CFG, batch_shape=[(64, 8, 4)], dtype="float32",
                       donation=(0,))
    assert k != cc.program_key(BASE_CFG, batch_shape=[(32, 8, 4)],
                               dtype="float32", donation=(0,))
    assert k != cc.program_key(BASE_CFG, batch_shape=[(64, 8, 4)],
                               dtype="bfloat16", donation=(0,))
    assert k != cc.program_key(BASE_CFG, batch_shape=[(64, 8, 4)],
                               dtype="float32", donation=())


def test_key_baked_hyperparams_become_structural():
    """inject_hyperparams=False bakes lr/wd into the HLO as constants — the
    key must split what the compiler splits."""
    a = dict(BASE_CFG, inject_hyperparams=False)
    b = dict(a, learning_rate=0.5)
    assert cc.program_key(a) != cc.program_key(b)
    # seed is a traced ARGUMENT either way: never structural.
    assert cc.program_key(a) == cc.program_key(dict(a, seed=123))


GOLDEN_KEY = "pk_8c850e7eb4de69d133dee5c989b42a74"


def test_key_golden_and_stable_across_processes():
    """The key is a pure content hash: identical in this process, in a
    fresh interpreter, and against the committed golden value — hosts can
    exchange artifacts by key only because of this."""
    kwargs = dict(batch_shape=[(64, 8, 4)], dtype="float32", donation=(0, 1))
    assert cc.program_key(BASE_CFG, **kwargs) == GOLDEN_KEY
    code = (
        "import json,sys\n"
        "from distributed_machine_learning_tpu.compilecache import "
        "program_key\n"
        f"cfg = json.loads({json.dumps(json.dumps(BASE_CFG))!s})\n"
        "cfg['hidden_sizes'] = tuple(cfg['hidden_sizes'])\n"
        "print(program_key(cfg, batch_shape=[(64, 8, 4)], dtype='float32',"
        " donation=(0, 1)))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert out.stdout.strip().splitlines()[-1] == GOLDEN_KEY


def test_key_tuple_list_agnostic():
    """Configs round-tripped through JSON (lists) and live configs (tuples)
    must agree — cluster frames ship configs through pickle/json freely."""
    assert cc.program_key(BASE_CFG) == cc.program_key(
        dict(BASE_CFG, hidden_sizes=[16, 8])
    )


# ---------------------------------------------------------------------------
# compiled-PBT generation-scan keys (ISSUE 9)
# ---------------------------------------------------------------------------

_PBT_SPEC = {
    "quantile": 0.25, "resample_p": 0.25, "factors": (0.8, 1.2),
    "keys": ["learning_rate"],
    "specs": [{"key": "learning_rate", "lo": 1e-3, "hi": 1e-1, "log": True}],
    "grid_points": 1024, "sign": 1.0,
}
PBT_GOLDEN_KEY = "pk_5f43c740785e3c9878f6b7ade4a87320"


def _pbt_key(cfg=None, **over):
    kwargs = dict(interval=2, generations=4, rows=8,
                  mutation_spec=_PBT_SPEC, batch_shape=[(64, 8, 4)])
    kwargs.update(over)
    return cc.pbt_program_key(cfg or BASE_CFG, **kwargs)


def test_pbt_key_golden_and_seed_invariant():
    """The generation-scan key is a pure content hash (committed golden),
    and the PBT/trial seeds must NOT split it — seeds ride in as per-row
    PRNG key ARGUMENTS, exactly like trial seeds in the base key, so one
    compiled scan serves every seeding of the same sweep shape."""
    assert _pbt_key() == PBT_GOLDEN_KEY
    assert _pbt_key(dict(BASE_CFG, seed=999, learning_rate=3.3,
                         weight_decay=0.0)) == PBT_GOLDEN_KEY


@pytest.mark.parametrize("change", [
    {"interval": 4},                       # inner scan trip count
    {"generations": 2},                    # outer scan trip count
    {"rows": 16},                          # population size
    {"objective": "quality_latency_params"},
    {"mutation_spec": dict(_PBT_SPEC, resample_p=0.5)},
    {"mutation_spec": dict(_PBT_SPEC, specs=[
        {"key": "learning_rate", "lo": 1e-4, "hi": 1e-1, "log": True}])},
])
def test_pbt_key_splits_on_scan_identity(change):
    assert _pbt_key(**change) != PBT_GOLDEN_KEY


_PBT_SWEEP_CODE = """
import json, os
import numpy as np
from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import Dataset

rng = np.random.default_rng(7)
x = rng.normal(size=(128, 8, 4)).astype(np.float32)
w = rng.normal(size=(4,)).astype(np.float32)
y = (x.mean(axis=1) @ w)[:, None].astype(np.float32)
train, val = Dataset(x[:96], y[:96]), Dataset(x[96:], y[96:])
space = {
    "model": "mlp", "hidden_sizes": (16, 8),
    "learning_rate": tune.loguniform(1e-3, 1e-1),
    "weight_decay": 1e-6, "seed": tune.randint(0, 10_000),
    "num_epochs": 12, "batch_size": 16, "loss_function": "mse",
    "lr_schedule": "constant",
}
pbt = tune.PopulationBasedTraining(
    perturbation_interval=1,
    hyperparam_mutations={"learning_rate": tune.loguniform(1e-3, 1e-1)},
    quantile_fraction=0.25, seed=3,
)
analysis = tune.run_vectorized(
    space, train_data=train, val_data=val,
    metric="validation_mse", mode="min", num_samples=8,
    scheduler=pbt, epochs_per_dispatch=3,  # 4 chunks x 3 generations
    storage_path=os.environ["SWEEP_DIR"], seed=2, verbose=0,
)
with open(os.path.join(analysis.root, "experiment_state.json")) as f:
    print(json.dumps(json.load(f)))
"""


def test_compiled_pbt_zero_recompile_across_generations(tmp_path):
    """Acceptance (ISSUE 9 satellite): generations >> uncached backend
    compiles.  A chunked compiled-PBT sweep re-dispatches ONE generation-
    scan program — the second chunk compiles nothing new.

    Runs in a FRESH process (honest compile census, and the big scan's
    fusions must not pollute this process's XLA CPU symbol registry —
    in-process, a later ``deserialize_executable`` can fail with
    'Symbols not found' and silently cost other tests a recompile)."""
    out = subprocess.run(
        [sys.executable, "-c", _PBT_SWEEP_CODE],
        env=dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu",
                 SWEEP_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    state = json.loads(out.stdout.strip().splitlines()[-1])
    pbt_block = state["pbt"]
    assert pbt_block["mode"] == "compiled"
    assert pbt_block["generations"] == 12
    assert pbt_block["host_dispatches"] == 4
    compile_block = state["compile"]
    # Program count: vmapped init + ONE generation scan (reused by all 4
    # chunks) + the handful of tiny eager helpers (key creation).  The
    # decisive property: uncached compiles stay far below the generation
    # count — the scan recompiles for NO generation and NO chunk.
    assert compile_block["backend_compiles_uncached"] <= 6
    assert (compile_block["backend_compiles_uncached"]
            < pbt_block["generations"])
    # The cross-chunk program cache registered 1 miss (first build) and
    # 3 hits for the generation scan.
    assert compile_block.get("program_hits", 0) >= 3


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------


def test_aot_roundtrip_and_counters(tmp_path):
    import jax.numpy as jnp

    counters = cc.get_counters()
    base = counters.snapshot()
    store = cc.ExecutableCache(str(tmp_path))
    fn = lambda x: x * 2 + 1  # noqa: E731
    x = jnp.ones((4,), jnp.float32)
    f1 = store.get_or_compile("pk_t1", fn, x)
    np.testing.assert_allclose(np.asarray(f1(x)), 3.0)
    # Fresh cache instance (a "restarted process" in-process): disk import.
    store2 = cc.ExecutableCache(str(tmp_path))
    assert "pk_t1" in store2
    f2 = store2.get_or_compile("pk_t1", fn, x)
    np.testing.assert_allclose(np.asarray(f2(x)), 3.0)
    d = counters.delta_since(base)
    assert d["program_misses"] == 1
    assert d["aot_exports"] == 1
    assert d["aot_imports"] == 1
    assert d["program_hits"] == 1
    assert store2.disk_keys() == ["pk_t1"]


def test_aot_corrupt_entry_recompiles(tmp_path):
    import jax.numpy as jnp

    store = cc.ExecutableCache(str(tmp_path))
    fn = lambda x: x - 1  # noqa: E731
    x = jnp.ones((3,), jnp.float32)
    store.get_or_compile("pk_bad", fn, x)
    path = os.path.join(str(tmp_path), "pk_bad.aotexec")
    with open(path, "wb") as f:
        f.write(b"DMLAOT1\n" + b"garbage")
    fresh = cc.ExecutableCache(str(tmp_path))
    g = fresh.get_or_compile("pk_bad", fn, x)  # must not raise
    np.testing.assert_allclose(np.asarray(g(x)), 0.0)


def test_aot_import_refused_at_first_call_recompiles(tmp_path, monkeypatch):
    """An imported executable the runtime refuses when its (async) result
    is awaited — XLA:CPU's "function not found" for a deserialized
    program — costs a recompile and is dropped, never an error."""
    import jax
    import jax.numpy as jnp

    store = cc.ExecutableCache(str(tmp_path))
    fn = lambda x: x + 2  # noqa: E731
    x = jnp.ones((3,), jnp.float32)
    store.get_or_compile("pk_refused", fn, x)

    def refused(*args):
        raise jax.errors.JaxRuntimeError("NOT_FOUND: Function f not found")

    fresh = cc.ExecutableCache(str(tmp_path))
    monkeypatch.setattr(fresh, "_load_from_disk", lambda key, devs: refused)
    base = cc.get_counters().snapshot()
    g = fresh.get_or_compile("pk_refused", fn, x)
    np.testing.assert_allclose(np.asarray(g(x)), 3.0)
    d = cc.get_counters().delta_since(base)
    assert d["aot_imports"] == 1 and d["aot_unsupported"] == 1
    assert "pk_refused" not in fresh


def test_aot_donated_program_roundtrip(tmp_path):
    import jax.numpy as jnp

    store = cc.ExecutableCache(str(tmp_path))

    def step(p, g):
        return p - 0.1 * g, (g * g).sum()

    p = jnp.ones((8, 8), jnp.float32)
    f = store.get_or_compile("pk_don", step, p, p, donate_argnums=(0,))
    out, s = f(jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32))
    assert float(s) == 64.0
    fresh = cc.ExecutableCache(str(tmp_path))
    f2 = fresh.get_or_compile("pk_don", step, p, p, donate_argnums=(0,))
    out, s = f2(jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32))
    assert float(s) == 64.0


# ---------------------------------------------------------------------------
# origin primitives
# ---------------------------------------------------------------------------


def test_install_artifacts_rejects_traversal(tmp_path):
    dest = tmp_path / "cache"
    dest.mkdir()
    n = cc.install_artifacts(str(dest), {
        "ok.bin": b"fine",
        "../escape.bin": b"nope",
        "sub/dir/entry.bin": b"fine too",
    })
    assert n == 2
    assert (dest / "ok.bin").exists()
    assert (dest / "sub" / "dir" / "entry.bin").exists()
    assert not (tmp_path / "escape.bin").exists()


def test_artifact_registry_first_publish_wins():
    reg = cc.ArtifactRegistry()
    assert reg.publish("pk_a", {"f": b"1"})
    assert not reg.publish("pk_a", {"f": b"2"})  # later copies add nothing
    assert reg.fetch("pk_a") == {"f": b"1"}
    assert reg.fetch("pk_missing") is None
    snap = reg.snapshot()
    assert snap["origin_publishes"] == 1
    assert snap["origin_fetch_hits"] == 1
    assert snap["origin_fetch_misses"] == 1
    assert snap["distinct_keys"] == 1


def test_snapshot_and_pack_roundtrip(tmp_path):
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.bin").write_bytes(b"aa")
    (src / "sub" / "b.bin").write_bytes(b"bb")
    names = cc.snapshot_cache_dir(str(src))
    assert names == {"a.bin", os.path.join("sub", "b.bin")}
    files = cc.pack_artifacts(str(src), sorted(names))
    dest = tmp_path / "dest"
    dest.mkdir()
    assert cc.install_artifacts(str(dest), files) == 2
    assert (dest / "sub" / "b.bin").read_bytes() == b"bb"


# ---------------------------------------------------------------------------
# compile-once, counter-verified (acceptance criterion 3a)
# ---------------------------------------------------------------------------

_TRIAL_DRIVER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from distributed_machine_learning_tpu import tune
from distributed_machine_learning_tpu.data import dummy_regression_data

train, val = dummy_regression_data(num_samples=120, seq_len=8, num_features=4)
analysis = tune.run(
    tune.with_parameters(tune.train_regressor, train_data=train, val_data=val),
    {"model": "mlp", "hidden_sizes": (16,), "learning_rate": 0.01,
     "num_epochs": 2, "batch_size": 32, "lr_schedule": "constant", "seed": 5},
    metric="validation_loss", num_samples=1,
    storage_path=sys.argv[1], compile_cache_dir=sys.argv[2], verbose=0,
)
state = json.load(open(os.path.join(analysis.root, "experiment_state.json")))
print(json.dumps(state["compile"]))
"""


def test_fresh_process_with_populated_cache_compiles_nothing(tmp_path):
    """THE compile-once assertion: run the same trial config in two fresh
    processes sharing one compile-cache dir.  Process 1 compiles; process 2
    must record ZERO uncached backend compiles (every compile request is a
    persistent-cache hit) — asserted from the experiment's own ``compile``
    counter block, not eyeballed."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cache = str(tmp_path / "xla")
    blocks = []
    for i in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _TRIAL_DRIVER,
             str(tmp_path / f"results{i}"), cache],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-800:]
        blocks.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = blocks
    assert cold["backend_compiles_uncached"] > 0  # process 1 really compiled
    assert warm["backend_compiles_uncached"] == 0, warm
    assert warm["persistent_cache_hits"] > 0


# ---------------------------------------------------------------------------
# pre-warmed runner pool
# ---------------------------------------------------------------------------


def test_prewarm_pool_spawns_warm_runners(tmp_path):
    from distributed_machine_learning_tpu import tune
    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=120, seq_len=8, num_features=4
    )
    analysis = tune.run(
        tune.with_parameters(
            tune.train_regressor, train_data=train, val_data=val
        ),
        {"model": "mlp", "hidden_sizes": (16,),
         "learning_rate": tune.loguniform(1e-3, 1e-2),
         "num_epochs": 2, "batch_size": 32, "lr_schedule": "constant"},
        metric="validation_loss", num_samples=3, max_concurrent=1,
        storage_path=str(tmp_path / "results"),
        compile_cache_dir=str(tmp_path / "xla"),
        trial_executor="process", prewarm_runners=2, verbose=0,
    )
    assert analysis.num_terminated() == 3
    state = json.load(
        open(os.path.join(analysis.root, "experiment_state.json"))
    )
    comp = state["compile"]
    # Initial fill is 2 and the pool replenishes on take: every trial of
    # this serialized sweep starts on a pre-warmed runner.
    assert comp.get("prewarmed_spawns", 0) >= 2, comp
    assert comp.get("cold_spawns", 0) <= 1, comp


def test_child_precompile_frame(tmp_path):
    """Protocol-level check of think-time precompile: a warm child answers
    a precompile frame with ("prewarmed", key, n) and still runs a normal
    trial afterwards."""
    import cloudpickle

    from distributed_machine_learning_tpu.tune import _process_child as pc

    def trainable(config):
        import jax
        import jax.numpy as jnp

        from distributed_machine_learning_tpu.tune import session

        y = float(jax.jit(lambda v: (v * config["learning_rate"]).sum())(
            jnp.ones((4,))
        ))
        session.report({"loss": y})

    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    env[pc.PREWARM_ENV] = "1"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "distributed_machine_learning_tpu.tune._process_child"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
        cwd=REPO_ROOT,
    )
    try:
        blob = cloudpickle.dumps(trainable)
        pc.write_frame(proc.stdin, ("precompile", {
            "key": "pk_unit", "trainable": blob,
            "config": {"learning_rate": 2.0}, "sys_path": [REPO_ROOT],
        }))
        assert pc.read_frame(proc.stdout) == ("warm",)
        kind, key, compiles = pc.read_frame(proc.stdout)
        assert (kind, key) == ("prewarmed", "pk_unit")
        # Now the real trial on the same (already hot) child.
        pc.write_frame(proc.stdin, {
            "trial_id": "t0", "config": {"learning_rate": 2.0},
            "trainable": blob, "restore": None, "sys_path": [REPO_ROOT],
        })
        kind, metrics, ckpt = pc.read_frame(proc.stdout)
        assert kind == "result" and metrics["loss"] == 8.0
        pc.write_frame(proc.stdin, ("decision", "stop"))
        assert pc.read_frame(proc.stdout)[0] == "complete"
    finally:
        proc.stdin.close()
        proc.wait(timeout=30)

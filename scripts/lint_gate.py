#!/usr/bin/env python3
"""CI diff gate: ``dml-tpu lint --changed --format=sarif`` for BOTH tiers.

The checked-in entry point CI (and pre-push hooks) call so the gate's
flags live in ONE place:

    python scripts/lint_gate.py [--ref REF] [--out lint.sarif] [--full]

* ``--ref`` (default: ``origin/main`` if it resolves, else ``HEAD``) —
  findings are filtered to files changed vs the ref; the whole tree is
  still parsed/audited so cross-file and program-level checks judge the
  change against the full project.
* ``--out`` — where the SARIF 2.1.0 report lands (CI annotators upload
  it); the human-readable text report goes to stdout either way.
* ``--full`` — gate the whole tree instead of the diff (the nightly /
  release mode).
* ``--no-jax`` — AST tier only, for hosts without a working jax install.
* ``--no-perf-guard`` — skip the obs-plane disabled-path overhead check.

The gate also runs the observability-plane overhead guard
(``DML_OBS_PERF_GUARD=1`` in its own environment): the tracing-DISABLED
``obs.span()`` path — no tracer and, jax being imported with the package,
the profiler bridge with no session running — must stay at a few hundred
ns per call with zero net allocation, or always-on instrumentation in
epoch/request hot paths stops being free — a regression there gates the
diff like a lint finding.

Exit code is the lint's: 0 clean, 1 unsuppressed findings, 2 usage/git
trouble — the same contract as ``dml-tpu lint`` itself.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _default_ref() -> str:
    probe = subprocess.run(
        ["git", "rev-parse", "--verify", "--quiet", "origin/main"],
        cwd=REPO, capture_output=True, text=True,
    )
    return "origin/main" if probe.returncode == 0 else "HEAD"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ref", default=None,
                   help="diff base (default: origin/main, else HEAD)")
    p.add_argument("--out", default="lint.sarif",
                   help="SARIF output path (default: ./lint.sarif)")
    p.add_argument("--full", action="store_true",
                   help="lint the whole tree, not just the diff")
    p.add_argument("--no-jax", action="store_true",
                   help="skip the program-level (jaxlint) tier")
    p.add_argument("--no-perf-guard", action="store_true",
                   help="skip the obs disabled-path overhead guard")
    p.add_argument("--no-quant-smoke", action="store_true",
                   help="skip the quantize-export-load smoke")
    p.add_argument("--no-loop-smoke", action="store_true",
                   help="skip the drift-retrain-promote loop smoke")
    p.add_argument("--no-head-smoke", action="store_true",
                   help="skip the head-crash auto-resume smoke")
    p.add_argument("--no-gang-smoke", action="store_true",
                   help="skip the 2-process gang serving smoke")
    p.add_argument("--no-store-smoke", action="store_true",
                   help="skip the content-store publish/dedup/gc smoke")
    args = p.parse_args(argv)

    cmd = [sys.executable, "-m", "distributed_machine_learning_tpu",
           "lint", "--format=sarif"]
    if not args.no_jax:
        cmd.append("--jax")
    if not args.full:
        cmd.append(f"--changed={args.ref or _default_ref()}")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")  # the gate must not touch TPUs
    proc = subprocess.run(
        cmd, cwd=REPO, capture_output=True, text=True, env=env,
    )
    if proc.stderr:
        sys.stderr.write(proc.stderr)
    out = proc.stdout.strip()
    if not out:
        return proc.returncode
    try:
        sarif = json.loads(out)
    except json.JSONDecodeError:
        # --changed with no .py files changed prints a plain line, not
        # SARIF; surface it and pass the exit code through.
        print(out)
        return proc.returncode
    with open(args.out, "w") as f:
        json.dump(sarif, f, indent=2)
        f.write("\n")
    results = sarif["runs"][0]["results"]
    live = [r for r in results if not r.get("suppressions")]
    for r in live:
        loc = r["locations"][0]["physicalLocation"]
        print(f"{loc['artifactLocation']['uri']}:"
              f"{loc['region']['startLine']}: {r['ruleId']} "
              f"{r['message']['text'].splitlines()[0]}")
    print(f"lint gate: {len(live)} live finding(s), "
          f"{len(results) - len(live)} suppressed/baselined "
          f"-> {args.out}")
    if proc.returncode == 0 and not args.no_perf_guard:
        rc = _obs_perf_guard(env)
        if rc:
            return rc
    if proc.returncode == 0 and not args.no_quant_smoke:
        rc = _quant_smoke(env)
        if rc:
            return rc
    if proc.returncode == 0 and not args.no_loop_smoke:
        rc = _loop_smoke(env)
        if rc:
            return rc
    if proc.returncode == 0 and not args.no_head_smoke:
        rc = _head_crash_smoke(env)
        if rc:
            return rc
    if proc.returncode == 0 and not args.no_gang_smoke:
        rc = _gang_serve_smoke(env)
        if rc:
            return rc
    if proc.returncode == 0 and not args.no_store_smoke:
        rc = _store_smoke(env)
        if rc:
            return rc
    return proc.returncode


# Generous CI bounds (shared-runner jitter); the tier-1 guard in
# tests/test_obs_plane.py measures the same function.
PERF_GUARD_NS_BUDGET = 1500.0
PERF_GUARD_BLOCK_BUDGET = 16


def _obs_perf_guard(env) -> int:
    """Run obs.disabled_path_overhead in a child (DML_OBS_PERF_GUARD=1)
    and fail the gate if the disabled span path regressed."""
    env = dict(env, DML_OBS_PERF_GUARD="1")
    code = (
        "import json\n"
        "from distributed_machine_learning_tpu import obs\n"
        "print(json.dumps(min((obs.disabled_path_overhead()\n"
        "      for _ in range(3)), key=lambda r: r['ns_per_span'])))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("obs perf guard: FAILED to run")
        return 1
    measured = json.loads(proc.stdout.strip().splitlines()[-1])
    ok = (
        measured["ns_per_span"] <= PERF_GUARD_NS_BUDGET
        and measured["net_blocks"] <= PERF_GUARD_BLOCK_BUDGET
    )
    print(
        f"obs perf guard: {measured['ns_per_span']:.0f} ns/span, "
        f"{measured['net_blocks']} net blocks over {measured['iters']} "
        f"disabled spans (budget {PERF_GUARD_NS_BUDGET:.0f} ns / "
        f"{PERF_GUARD_BLOCK_BUDGET} blocks) -> "
        f"{'ok' if ok else 'REGRESSED'}"
    )
    return 0 if ok else 1


def _quant_smoke(env) -> int:
    """Quantize-export-load roundtrip in a child (JAX_PLATFORMS=cpu): a
    tiny mlp quantizes to int8, writes a bundle, loads it back, and the
    served predictions stay within the calibrated delta — the quant/
    manifest contract, gated like a lint finding."""
    code = (
        "import json, tempfile\n"
        "import jax, numpy as np\n"
        "from distributed_machine_learning_tpu import quant, serve\n"
        "from distributed_machine_learning_tpu.models import build_model\n"
        "from distributed_machine_learning_tpu.serve import export as ex\n"
        "config = {'model': 'mlp', 'hidden_sizes': [8]}\n"
        "model = build_model(config)\n"
        "x = np.random.default_rng(0).normal(\n"
        "    size=(8, 6, 4)).astype(np.float32)\n"
        "variables = model.init(jax.random.PRNGKey(0), x,\n"
        "                       deterministic=True)\n"
        "block = quant.build_quant_block(model, variables, 'int8', x)\n"
        "qvars = block.pop('_variables')\n"
        "out = tempfile.mkdtemp(prefix='quant_smoke_')\n"
        "ex.write_bundle(out, {'bundle_version': ex.BUNDLE_VERSION,\n"
        "                      'config': config, 'precision': 'int8',\n"
        "                      'quant': block}, qvars)\n"
        "bundle = serve.load_bundle(out)\n"
        "assert bundle.precision == 'int8'\n"
        "eng = serve.InferenceEngine(bundle, max_bucket=8,\n"
        "                            persistent_cache=False)\n"
        "q = eng.predict(x)\n"
        "f = np.asarray(model.apply(variables, x, deterministic=True))\n"
        "mape = float(np.mean(np.abs(q - f) / (np.abs(f) + 1e-8)))\n"
        "delta = bundle.quality_delta_mape\n"
        "assert mape <= delta * 1.5 + 1e-3, (mape, delta)\n"
        "print(json.dumps({'quality_delta_mape': round(delta, 6),\n"
        "                  'served_mape': round(mape, 6),\n"
        "                  'compression': block.get('compression')}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("quant smoke: FAILED")
        return 1
    print(f"quant smoke: ok {proc.stdout.strip().splitlines()[-1]}")
    return 0


def _loop_smoke(env) -> int:
    """One self-healing episode in a child (JAX_PLATFORMS=cpu): a tiny
    served mlp drifts, the monitor triggers, and the controller's
    journaled retrain-gate-swap-probation episode must land PROMOTED
    with zero serving-path compiles — the loop/ contract, gated like a
    lint finding."""
    code = (
        "import json, os, tempfile\n"
        "import numpy as np\n"
        "from distributed_machine_learning_tpu import chaos, loop, serve\n"
        "from distributed_machine_learning_tpu.models import build_model\n"
        "from distributed_machine_learning_tpu.serve import export as ex\n"
        "from distributed_machine_learning_tpu.tune._regression_program \\\n"
        "    import detect_call_convention\n"
        "W = np.array([0.7, -0.4, 1.1], np.float32)\n"
        "DRIFT = {'at_request': 0, 'feature_shift': 2.5,\n"
        "         'label_shift': 0.5, 'seed': 11}\n"
        "def make_xy(n, seed, drifted=False):\n"
        "    r = np.random.default_rng(seed)\n"
        "    x = r.standard_normal((n, 4, 3)).astype(np.float32)\n"
        "    y = (x[:, -2:, :] @ W).mean(axis=1, keepdims=True)\n"
        "    if drifted:\n"
        "        x, y = chaos.apply_drift(DRIFT, x, y)\n"
        "    return x.astype(np.float32), y.astype(np.float32)\n"
        "def data_fn(kind):\n"
        "    seeds = {'train': 100, 'holdout': 200, 'probation': 300}\n"
        "    return make_xy(48, seeds[kind], drifted=True)\n"
        "config = {'model': 'mlp', 'hidden_sizes': [8], 'seed': 3}\n"
        "x, y = make_xy(64, 1)\n"
        "probe, _ = detect_call_convention(build_model(config), x[:1])\n"
        "variables, _ = loop.fine_tune(config, {'params': probe['params']},\n"
        "                              x, y, epochs=4, learning_rate=0.05,\n"
        "                              seed=0)\n"
        "root = tempfile.mkdtemp(prefix='loop_smoke_')\n"
        "inc = os.path.join(root, 'incumbent')\n"
        "ex.write_bundle(inc, {'bundle_version': ex.BUNDLE_VERSION,\n"
        "                      'config': config, 'precision': 'f32'},\n"
        "                variables)\n"
        "srv = serve.PredictionServer(serve.load_bundle(inc), port=0,\n"
        "                             num_replicas=1, max_bucket=16)\n"
        "srv.warmup(x[:1])\n"
        "drift = loop.DriftMonitor(window=16, z_threshold=4.0, sustain=3)\n"
        "srv.metrics.attach_drift(drift)\n"
        "for i in range(40):\n"
        "    xb, _ = make_xy(4, 1000 + i, drifted=i >= 18)\n"
        "    preds = np.asarray(srv.replicas.predict(xb))\n"
        "    srv.metrics.observe_streams(float(np.mean(xb)),\n"
        "                                float(np.mean(preds)))\n"
        "ctl = loop.SelfHealingController(\n"
        "    srv, loop.LoopJournal(os.path.join(root, 'loop.json')),\n"
        "    drift, data_fn, root,\n"
        "    loop.LoopConfig(retrain_epochs=3, probation_batches=2))\n"
        "outcome = ctl.poll()\n"
        "assert outcome is not None, 'drift never triggered'\n"
        "assert outcome['state'] == 'promoted', outcome\n"
        "stats = srv.replicas.program_stats()\n"
        "assert stats['new_programs_since_warmup'] == 0, stats\n"
        "srv.close()\n"
        "print(json.dumps({'state': outcome['state'],\n"
        "                  'probation_mape':\n"
        "                      round(outcome['probation_mape'], 4),\n"
        "                  'incumbent_mape':\n"
        "                      round(outcome['incumbent_mape'], 4)}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("loop smoke: FAILED")
        return 1
    print(f"loop smoke: ok {proc.stdout.strip().splitlines()[-1]}")
    return 0


def _head_crash_smoke(env) -> int:
    """Durable-control-plane smoke in a child (JAX_PLATFORMS=cpu): a tiny
    sweep's driver is killed (``os._exit(86)`` mid-journal-append, the
    chaos ``kill_head_at`` fault) at decision 4, ``resume="auto"``
    replays the write-ahead journal, and the finished experiment must
    name the SAME best trial as an uninterrupted control — the tune
    journal contract, gated like a lint finding."""
    code = (
        "import json, tempfile\n"
        "from distributed_machine_learning_tpu.tune import crashsim\n"
        "root = tempfile.mkdtemp(prefix='head_crash_smoke_')\n"
        "spec = dict(num_samples=3, epochs=3, seed=5)\n"
        "ctrl = crashsim.control_run(root, 'ctrl', **spec)\n"
        "out = crashsim.killed_then_resumed(root, 'crash', kill_at=4,\n"
        "                                   **spec)\n"
        "assert out['crash_rc'] == crashsim.HEAD_KILL_EXIT\n"
        "res = out['result']\n"
        "assert res['best_trial'] == ctrl['best_trial'], (res, ctrl)\n"
        "assert res['best_score'] == ctrl['best_score'], (res, ctrl)\n"
        "assert out['journal']['committed'] is True\n"
        "assert out['journal']['head_starts'] == 2\n"
        "print(json.dumps({'best_trial': res['best_trial'],\n"
        "                  'detect_s': out['detect_s'],\n"
        "                  'replay_s': out['replay_s'],\n"
        "                  'requeue_s': out['requeue_s']}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("head-crash smoke: FAILED")
        return 1
    print(f"head-crash smoke: ok {proc.stdout.strip().splitlines()[-1]}")
    return 0


def _gang_serve_smoke(env) -> int:
    """Pod-scale serving smoke in a child (JAX_PLATFORMS=cpu): a 2-process
    serving GANG loads a TP-sharded bundle, reshards it onto the spanning
    mesh, and must answer bit-identically to the single-process engine
    with ZERO serving-path compiles after warmup — the serve/gang
    contract, gated like a lint finding.  Containers that cannot run
    2-process jax.distributed over CPU collectives skip (rc 0) WITH the
    probe's evidence, same as the tier-1 gang tests."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    try:
        import _env_probe
        ok, why = _env_probe.multiprocess_cpu_collectives()
    finally:
        sys.path.remove(os.path.join(REPO, "tests"))
    if not ok:
        print(f"gang smoke: skipped (2-process jax.distributed "
              f"unavailable here: {why})")
        return 0
    # Dense_0 column-sharded into a WIDER Dense_1: propagation all-gathers
    # the narrow activations (exact) instead of psumming wide partials, so
    # the gang must match the single-process engine bit for bit.
    code = (
        "import json, tempfile\n"
        "import jax, numpy as np\n"
        "from distributed_machine_learning_tpu import serve\n"
        "from distributed_machine_learning_tpu.models import build_model\n"
        "from distributed_machine_learning_tpu.serve import export as ex\n"
        "from distributed_machine_learning_tpu.serve.gang import "
        "GangReplica\n"
        "config = {'model': 'mlp', 'hidden_sizes': [16, 64],\n"
        "          'partition_rules': [\n"
        "              ['params/Dense_0/kernel', [None, 'tp']],\n"
        "              ['params/Dense_0/bias', ['tp']],\n"
        "              ['.*', []]]}\n"
        "model = build_model(config)\n"
        "x = np.random.default_rng(0).normal(\n"
        "    size=(5, 6, 4)).astype(np.float32)\n"
        "variables = model.init(jax.random.PRNGKey(0), x,\n"
        "                       deterministic=True)\n"
        "out = tempfile.mkdtemp(prefix='gang_smoke_')\n"
        "ex.write_bundle(out, {'bundle_version': ex.BUNDLE_VERSION,\n"
        "                      'config': config, 'precision': 'f32'},\n"
        "                variables)\n"
        "bundle = serve.load_bundle(out)\n"
        "ref = serve.InferenceEngine(bundle, max_bucket=8,\n"
        "                            persistent_cache=False).predict(x)\n"
        "gang = GangReplica(0, bundle, processes=2, max_bucket=8)\n"
        "try:\n"
        "    warm = gang.warmup(x)\n"
        "    assert warm['topology']['process_count'] == 2, warm\n"
        "    got = gang.submit(x).result(timeout=120)\n"
        "    assert np.array_equal(got, ref), 'gang != single-process'\n"
        "    stats = gang.engine.program_stats()\n"
        "    assert stats['programs'] == warm['programs'], (\n"
        "        'serving-path compile after warmup', stats)\n"
        "finally:\n"
        "    gang.retire()\n"
        "print(json.dumps({'processes': 2,\n"
        "                  'programs': warm['programs'],\n"
        "                  'bit_identical': True}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=480,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("gang smoke: FAILED")
        return 1
    print(f"gang smoke: ok {proc.stdout.strip().splitlines()[-1]}")
    return 0


def _store_smoke(env) -> int:
    """Content-store smoke in a child (JAX_PLATFORMS=cpu): two checkpoint
    generations that share a leaf publish through the store (the second
    save must be a dedup hit, not a second copy), load back bit-identical,
    GC after deleting generation 1 reclaims only its unique blobs, and
    verify re-hashes clean — the store/ contract, gated like a lint
    finding."""
    code = (
        "import json, os, tempfile\n"
        "import numpy as np\n"
        "from distributed_machine_learning_tpu import store\n"
        "from distributed_machine_learning_tpu.ckpt import format as fmt\n"
        "root = tempfile.mkdtemp(prefix='store_smoke_')\n"
        "tree1 = {'w': np.arange(4096, dtype=np.float32),\n"
        "         'b': np.ones(512, np.float32)}\n"
        "tree2 = {'w': tree1['w'],  # unchanged -> dedup hit\n"
        "         'b': np.full(512, 2.0, np.float32)}\n"
        "g1 = os.path.join(root, 'gen_000001')\n"
        "g2 = os.path.join(root, 'gen_000002')\n"
        "before = store.get_metrics().snapshot()\n"
        "fmt.save_sharded(g1, tree1)\n"
        "fmt.save_sharded(g2, tree2)\n"
        "d = store.get_metrics().delta_since(before)\n"
        "assert d['dedup_hits'] > 0, d\n"
        "assert d['bytes_physical'] < d['bytes_logical'], d\n"
        "got = fmt.load_sharded(g2)\n"
        "assert np.array_equal(np.asarray(got['w']), tree2['w'])\n"
        "assert np.array_equal(np.asarray(got['b']), tree2['b'])\n"
        "cas = store.get_store(store.store_root_for(g1))\n"
        "fmt.delete_generation(g1)\n"
        "swept = cas.gc()\n"
        "assert swept['collected'] > 0 and swept['retained'] > 0, swept\n"
        "got = fmt.load_sharded(g2)  # survivor still loads post-GC\n"
        "assert np.array_equal(np.asarray(got['b']), tree2['b'])\n"
        "checked = cas.verify()\n"
        "assert not checked['corrupt'], checked\n"
        "print(json.dumps({'dedup_hits': d['dedup_hits'],\n"
        "                  'bytes_logical': d['bytes_logical'],\n"
        "                  'bytes_physical': d['bytes_physical'],\n"
        "                  'gc_collected': swept['collected'],\n"
        "                  'verified_blobs': checked['blobs']}))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        print("store smoke: FAILED")
        return 1
    print(f"store smoke: ok {proc.stdout.strip().splitlines()[-1]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

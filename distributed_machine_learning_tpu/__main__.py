"""Package CLI: ``python -m distributed_machine_learning_tpu <command>``.

The reference's launch surface is ``python <script>.py`` (SURVEY.md §1 L5);
the framework keeps that for experiment drivers (your script calls
``tune.run``) and adds the operational commands a multi-host deployment
needs:

* ``worker`` — start a host trial supervisor (or ``--join`` a driver
  elastically); forwards to ``tune.cluster``'s CLI.
* ``info`` — print the jax backend/device/mesh view of THIS process, the
  first thing to check when a pod host misbehaves.
* ``export-orbax <ckpt.msgpack> <out_dir>`` — convert a framework
  checkpoint to an orbax StandardCheckpoint for orbax-consuming stacks.
* ``probe [--timeout S]`` — bounded accelerator health check in a CHILD
  process (a hung backend times out instead of hanging this shell; the
  child is killed at the limit). Exit 0 = an accelerator executed a real
  computation; 1 = healthy but CPU-only; 2 = the probe child crashed
  (broken install/plugin); 124 = backend hung.  Run it from a process
  that has not touched jax: a chip belongs to one process at a time.

Note on startup cost: ``python -m`` imports the package ``__init__`` (and
with it jax/flax/optax) before this module runs, so even ``--help`` pays
the framework import — the in-function imports below are for readability,
not deferral; there is no way to dodge an eager package ``__init__``
under ``-m``.
"""

from __future__ import annotations

import json
import sys


def _info() -> None:
    import jax

    devs = jax.devices()
    out = {
        "backend": jax.default_backend(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": len(devs),
        "device_kinds": sorted({d.device_kind for d in devs}),
    }
    try:
        from distributed_machine_learning_tpu.ops.flops import (
            device_peak_flops,
        )

        out["peak_flops_f32"] = device_peak_flops(devs[0])
        out["peak_flops_bf16"] = device_peak_flops(devs[0], "bfloat16")
    except Exception:  # noqa: BLE001 - info must print what it can
        pass
    print(json.dumps(out, indent=2))


def _probe(rest) -> None:
    import argparse
    import subprocess

    p = argparse.ArgumentParser(prog="probe")
    p.add_argument("--timeout", type=float, default=120.0)
    args = p.parse_args(rest)
    code = (
        "import jax, jax.numpy as jnp, json\n"
        "d = jax.devices()[0]\n"
        "ok = float(jnp.ones((8, 8)).sum()) == 64.0\n"
        "print(json.dumps({'platform': d.platform,\n"
        "                  'device_kind': getattr(d, 'device_kind', None),\n"
        "                  'devices': jax.device_count(),\n"
        "                  'executed': ok}))\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        out, err = proc.communicate(timeout=args.timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print(json.dumps({
            "error": f"backend init/execute hung past {args.timeout}s "
                     f"(child killed)",
        }))
        raise SystemExit(124)
    line = (out.strip().splitlines() or [""])[-1]
    try:
        res = json.loads(line)
    except json.JSONDecodeError:
        # Distinct from "healthy CPU-only host" (exit 1): the child CRASHED
        # (broken install, bad plugin) — a pod-health script must not read
        # that as fine-but-no-accelerator.
        print(json.dumps({"error": (err or out)[-400:]}))
        raise SystemExit(2) from None
    print(json.dumps(res))
    healthy_accel = res.get("platform") != "cpu" and res.get("executed") is True
    raise SystemExit(0 if healthy_accel else 1)


def _analyze(rest) -> None:
    import argparse
    import os

    p = argparse.ArgumentParser(prog="analyze")
    p.add_argument("experiment_dir",
                   help="an experiment directory (<storage_path>/<name>)")
    p.add_argument("--metric", default=None,
                   help="objective (default: the one recorded in "
                        "experiment_state.json)")
    p.add_argument("--mode", default=None, choices=("min", "max"))
    p.add_argument("--rows", type=int, default=10)
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu.tune.experiment import (
        ExperimentAnalysis,
    )

    root = args.experiment_dir
    if not os.path.isdir(root):  # diagnose a typo'd path FIRST
        print(f"error: no experiment directory at {root}", file=sys.stderr)
        raise SystemExit(1)
    state = {}
    state_path = os.path.join(root, "experiment_state.json")
    if os.path.exists(state_path):
        with open(state_path) as f:
            state = json.load(f)
    metric = args.metric or state.get("metric")
    mode = args.mode or state.get("mode") or "min"
    if not metric:
        print("error: experiment predates metric recording — pass --metric",
              file=sys.stderr)
        raise SystemExit(2)
    analysis = ExperimentAnalysis.from_directory(root, metric, mode)
    if not analysis.trials:
        print(f"error: no trials under {root}", file=sys.stderr)
        raise SystemExit(1)
    if not any(metric in r for t in analysis.trials for r in t.results):
        print(f"error: no trial reported metric {metric!r} under {root}",
              file=sys.stderr)
        raise SystemExit(1)
    if args.json:
        try:
            best_config, best_result = analysis.best_config, analysis.best_result
        except ValueError as exc:  # e.g. a typo'd --metric no trial reported
            print(f"error: {exc}", file=sys.stderr)
            raise SystemExit(1) from None
        print(json.dumps({
            "metric": metric,
            "mode": mode,
            "num_trials": len(analysis.trials),
            "num_terminated": analysis.num_terminated(),
            "best_config": best_config,
            "best_result": best_result,
            **{k: state[k] for k in (
                "wall_clock_s", "device_utilization",
                "compile_time_total_s", "compile_cache_hits",
            ) if k in state},
        }))
        return
    # Human view: reuse the ProgressReporter's final table verbatim.
    from distributed_machine_learning_tpu.tune.callbacks import (
        ProgressReporter,
    )

    # inf interval: no live re-renders while replaying — only the final
    # summary table prints.
    rep = ProgressReporter(interval_s=float("inf"), max_rows=args.rows)
    rep.setup(root, metric, mode)
    for t in analysis.trials:
        for r in t.results:
            rep.on_trial_result(t, r)
    rep.on_experiment_end(analysis.trials, state.get("wall_clock_s", 0.0))


def _lint(rest) -> None:
    import argparse
    import os

    p = argparse.ArgumentParser(
        prog="lint",
        description="dmlint: project-native static analysis "
                    "(docs/static-analysis.md)",
    )
    p.add_argument("paths", nargs="*", default=None,
                   help="files/directories to lint (default: the installed "
                        "package tree)")
    p.add_argument("--rule", action="append", default=None,
                   help="run only this rule (name or id; repeatable)")
    p.add_argument("--baseline", default=None,
                   help="baseline file (default: analysis/baseline.json; "
                        "'none' disables)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to absorb every current "
                        "unsuppressed finding (burn-down workflow)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable findings (includes suppressed/"
                        "baselined, marked); alias for --format=json")
    p.add_argument("--format", default=None,
                   choices=("text", "json", "sarif"),
                   help="report format (default: text; sarif = SARIF "
                        "2.1.0 for CI annotators)")
    p.add_argument("--changed", nargs="?", const="HEAD", default=None,
                   metavar="REF",
                   help="lint only files touched vs a git ref (default "
                        "HEAD) — the fast pre-commit path; the whole "
                        "tree is still parsed so cross-file rules see "
                        "the full call graph, and exit codes match the "
                        "full run")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="also show suppressed and baselined findings")
    p.add_argument("--jax", action="store_true",
                   help="ALSO run the program-level tier (jaxlint, "
                        "docs/static-analysis.md): partition-rule "
                        "coverage, donation verification, jaxpr hygiene, "
                        "mesh-axis soundness — imports jax but compiles "
                        "and allocates nothing")
    args = p.parse_args(rest)
    fmt = args.format or ("json" if args.json else "text")

    # The linter is stdlib-only on purpose: importing the analysis package
    # pulls in no jax (engine.py docstring) — `dml-tpu lint` stays usable
    # on hosts where backend init is broken (which is WHEN you lint).
    # --jax opts into the program-level tier and is the one path that
    # imports jax (still: eval_shape/make_jaxpr/lower only, nothing run).
    from distributed_machine_learning_tpu import analysis

    paths = args.paths or [
        os.path.dirname(os.path.abspath(analysis.__file__)) + "/.."
    ]
    # --rule restricts BOTH tiers: each name resolves to an AST rule or a
    # jax check; naming a jax check implies --jax.  A tier with no
    # selected rules is skipped entirely.
    rules = jax_checks = None
    if args.rule:
        rules, jax_checks = [], []
        for r in args.rule:
            try:
                rules.append(analysis.get_rule(r))
                continue
            except KeyError:
                pass
            try:
                jax_checks.append(analysis.get_jax_check(r))
                args.jax = True
            except KeyError:
                print(f"error: no dmlint rule or jaxlint check named "
                      f"{r!r}", file=sys.stderr)
                raise SystemExit(2) from None
    baseline = args.baseline or analysis.DEFAULT_BASELINE
    if baseline == "none":
        baseline = None
    only_files = None
    if args.changed is not None:
        only_files = _changed_python_files(args.changed, paths)
        if only_files is None:
            raise SystemExit(2)  # not a git checkout / bad ref
        if not only_files:
            print(f"dmlint: no .py files changed vs {args.changed}")
            raise SystemExit(0)
    if rules is not None and not rules:
        result = analysis.LintResult()  # only jax checks were selected
    else:
        result = analysis.lint_paths(
            paths, rules=rules, baseline_path=baseline,
            only_files=only_files,
        )
    if args.jax and (jax_checks is None or jax_checks):
        jres = analysis.run_jax_checks(
            checks=jax_checks, baseline_path=baseline,
            only_files=only_files,
        )
        result.findings.extend(jres.findings)
        result.errors.extend(jres.errors)
        result.files_checked += jres.files_checked
        result.findings.sort(key=lambda f: (f.file, f.line, f.rule_id))
    if args.update_baseline:
        if baseline is None:
            print("error: --update-baseline needs a baseline path",
                  file=sys.stderr)
            raise SystemExit(2)
        analysis.save_baseline(baseline, result.unsuppressed())
        print(f"baseline rewritten: {baseline} "
              f"({len(result.unsuppressed())} entries)")
        return
    if fmt == "json":
        print(json.dumps({
            "files_checked": result.files_checked,
            "findings": [f.to_json() for f in result.findings],
            "errors": result.errors,
            "ok": result.ok,
        }, indent=2))
    elif fmt == "sarif":
        catalog = list(rules) if rules is not None else list(
            analysis.ALL_RULES
        )
        if args.jax:
            catalog += (
                list(jax_checks) if jax_checks
                else analysis.jax_check_catalog()
            )
        print(json.dumps(analysis.render_sarif(result, catalog), indent=2))
    else:
        print(analysis.render(result, verbose=args.verbose))
    raise SystemExit(0 if result.ok else 1)


def _audit_sharding(rest) -> None:
    """``dml-tpu audit-sharding``: the jax tier plus per-family coverage
    reports — the operator view of ``lint --jax`` (same gate, same exit
    semantics, with the sharding arithmetic printed instead of implied)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="audit-sharding",
        description="program-level sharding/donation audit (jaxlint; "
                    "alias for the jax tier of `lint --jax` plus "
                    "per-family partition coverage reports)",
    )
    p.add_argument("families", nargs="*", default=None,
                   help="model families to report on (default: every "
                        "registered family with canonical configs)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable reports + findings")
    p.add_argument("--baseline", default=None,
                   help="baseline file (default: analysis/baseline.json; "
                        "'none' disables)")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu import analysis
    from distributed_machine_learning_tpu.analysis.jaxlint import (
        coverage as coverage_lib,
    )
    from distributed_machine_learning_tpu.models.partition_rules import (
        PARTITION_RULE_TABLES,
    )

    families = args.families or sorted(
        f for f in coverage_lib.KNOWN_FAMILY_CONFIGS
        if f in PARTITION_RULE_TABLES
    )
    reports = []
    for family in families:
        if family not in PARTITION_RULE_TABLES:
            print(f"error: no partition-rule table for family "
                  f"{family!r}", file=sys.stderr)
            raise SystemExit(2)
        reports.append(coverage_lib.coverage_report(family))
    # A shared table's rule is dead only if NO audited family fires it
    # (the same union the lint gate applies) — the report must not claim
    # debt the gate would not.
    fired_union = {}
    for rep in reports:
        key = (rep["anchor_path"], rep["anchor_symbol"])
        fired_union.setdefault(key, set()).update(rep["fired"])
    for rep in reports:
        key = (rep["anchor_path"], rep["anchor_symbol"])
        rep["dead_rules"] = [
            d for d in rep["dead_rules"]
            if d["index"] not in fired_union[key]
        ]
    baseline = args.baseline or analysis.DEFAULT_BASELINE
    if baseline == "none":
        baseline = None
    result = analysis.run_jax_checks(baseline_path=baseline)
    if args.json:
        print(json.dumps({
            "reports": reports,
            "findings": [f.to_json() for f in result.findings],
            "errors": result.errors,
            "inert": result.inert,
            "ok": result.ok,
        }, indent=2))
        raise SystemExit(0 if result.ok else 1)
    for rep in reports:
        covered = rep["num_leaves"] - len(rep["unmatched"])
        print(f"[{rep['family']}] {rep['num_rules']} rule(s), "
              f"{rep['num_leaves']} non-scalar leaves over configs "
              f"({', '.join(rep['configs'])}): {covered} covered, "
              f"{len(rep['unmatched'])} unmatched, "
              f"{len(rep['dead_rules'])} dead rule(s), "
              f"{len(rep['non_dividing'])} non-dividing")
        for u in rep["unmatched"]:
            print(f"    unmatched: {u['path']} {u['shape']} "
                  f"({100 * u['fraction']:.1f}%, {u['config']})")
        for d in rep["dead_rules"]:
            print(f"    dead: {d['pattern']}")
        for n in rep["non_dividing"]:
            print(f"    non-dividing: {n['path']} dim {n['dim']} vs "
                  f"{n['axis']} of {n['mesh']}")
    print(analysis.render(result))
    print(f"jaxlint inert: {result.inert}")
    raise SystemExit(0 if result.ok else 1)


def _changed_python_files(ref, paths):
    """Absolute paths of ``.py`` files changed vs ``ref`` (committed diff
    + working tree + untracked), or None when git/ref is unusable.  The
    repo is found from the first lint path, so ``dml-tpu lint pkg/
    --changed`` works from anywhere inside the checkout."""
    import os
    import subprocess

    anchor = os.path.abspath(paths[0])
    cwd = anchor if os.path.isdir(anchor) else os.path.dirname(anchor)
    try:
        root = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"],
            cwd=cwd, capture_output=True, text=True, timeout=30,
        )
    except (OSError, subprocess.TimeoutExpired) as exc:
        print(f"error: --changed needs git: {exc}", file=sys.stderr)
        return None
    if root.returncode != 0:
        print(f"error: --changed outside a git checkout: "
              f"{root.stderr.strip()}", file=sys.stderr)
        return None
    top = root.stdout.strip()
    out = []
    for cmd in (
        ["git", "diff", "--name-only", ref, "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(
            cmd, cwd=top, capture_output=True, text=True, timeout=60,
        )
        if proc.returncode != 0:
            print(f"error: {' '.join(cmd)}: {proc.stderr.strip()}",
                  file=sys.stderr)
            return None
        out.extend(
            line.strip() for line in proc.stdout.splitlines()
            if line.strip().endswith(".py")
        )
    return sorted({os.path.join(top, rel) for rel in out})


def _trace(rest) -> None:
    """``dml-tpu trace {export|merge|summarize}``: the operator surface of
    the observability plane (obs/, docs/observability.md)."""
    import argparse
    import os

    p = argparse.ArgumentParser(
        prog="trace",
        description="export / merge / summarize structured traces "
                    "(tune.run(trace=True) or DML_OBS_TRACE=1)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    p_exp = sub.add_parser(
        "export",
        help="merge an experiment's per-process span files into one "
             "Chrome-trace/Perfetto trace.json",
    )
    p_exp.add_argument("experiment_dir",
                       help="an experiment directory (or its trace/ dir)")
    p_exp.add_argument("-o", "--out", default=None,
                       help="output path (default: <trace_dir>/trace.json)")

    p_merge = sub.add_parser(
        "merge",
        help="merge trace dirs/experiment dirs from several hosts into "
             "one trace.json",
    )
    p_merge.add_argument("sources", nargs="+",
                         help="trace directories (or experiment dirs)")
    p_merge.add_argument("-o", "--out", required=True)

    p_sum = sub.add_parser(
        "summarize",
        help="per-phase wall-clock breakdown table (one trial with "
             "--trial; the MFU 'where did the time go' view)",
    )
    p_sum.add_argument("source",
                       help="experiment dir, trace dir, or trace.json")
    p_sum.add_argument("--trial", default=None,
                       help="restrict to spans of one trial id")
    p_sum.add_argument("--json", action="store_true")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu import obs

    def resolve_trace_dir(path):
        sub_dir = os.path.join(path, "trace")
        return sub_dir if os.path.isdir(sub_dir) else path

    if args.cmd == "export":
        trace_dir = resolve_trace_dir(args.experiment_dir)
        if not os.path.isdir(trace_dir):
            print(f"error: no directory at {trace_dir}", file=sys.stderr)
            raise SystemExit(1)
        out = obs.merge_trace_dir(trace_dir, args.out)
        if out is None:
            print(f"error: no trace_*.jsonl span files under {trace_dir} "
                  f"(was the run traced? tune.run(trace=True) or "
                  f"DML_OBS_TRACE=1)", file=sys.stderr)
            raise SystemExit(1)
        print(out)
    elif args.cmd == "merge":
        records = []
        for src in args.sources:
            trace_dir = resolve_trace_dir(src)
            if not os.path.isdir(trace_dir):
                print(f"error: no directory at {trace_dir}",
                      file=sys.stderr)
                raise SystemExit(1)
            records.extend(obs.read_trace_files(trace_dir))
        if not records:
            print("error: no span records in any source", file=sys.stderr)
            raise SystemExit(1)
        with open(args.out, "w") as f:
            json.dump(obs.chrome_trace(records), f)
        print(args.out)
    else:
        try:
            rows, table = obs.summarize_trace(args.source, trial=args.trial)
        except (OSError, ValueError, KeyError) as exc:
            print(f"error: cannot summarize {args.source}: {exc}",
                  file=sys.stderr)
            raise SystemExit(1) from None
        if args.json:
            print(json.dumps({"trial": args.trial, "phases": rows}))
        else:
            if args.trial:
                print(f"trial {args.trial}:")
            print(table)


def _perf(rest) -> None:
    """``dml-tpu perf {compare|audit}``: the operator surface of the
    performance observatory (perf/, docs/performance.md)."""
    import argparse
    import glob as glob_lib

    p = argparse.ArgumentParser(
        prog="perf",
        description="cost-model audit + bench regression sentinel "
                    "(perf/costmodel.py, perf/sentinel.py)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    p_cmp = sub.add_parser(
        "compare",
        help="bucket BENCH_r*/MULTICHIP_r* rounds into comparability "
             "classes and verdict only within a class (exit 1 on an "
             "in-class regression beyond the noise band)",
    )
    p_cmp.add_argument("--artifacts", nargs="+", required=True,
                       help="round artifact paths or globs "
                            "(BENCH_r*.json MULTICHIP_r*.json)")
    p_cmp.add_argument("--noise", type=float, default=None,
                       help="noise band as a fraction (default 0.15: "
                            "+/-15%% is flat, not a verdict)")
    p_cmp.add_argument("--json", action="store_true")

    p_aud = sub.add_parser(
        "audit",
        help="compile tiny canonical programs per model family on THIS "
             "backend and cross-check XLA's cost_analysis() FLOPs "
             "against the analytic model in ops/flops.py (exit 1 on "
             "divergence beyond tolerance)",
    )
    p_aud.add_argument("families", nargs="*",
                       default=None,
                       help="model families (default: mlp "
                            "simple_transformer transformer)")
    p_aud.add_argument("--tolerance", type=float, default=None,
                       help="ratio tolerance (default "
                            "perf.DEFAULT_CROSSCHECK_TOL)")
    p_aud.add_argument("--json", action="store_true")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu import perf

    if args.cmd == "compare":
        paths = []
        for pat in args.artifacts:
            hits = sorted(glob_lib.glob(pat))
            paths.extend(hits if hits else [pat])
        rounds = perf.load_rounds(paths)
        if not rounds:
            print(f"error: no BENCH_r*/MULTICHIP_r* artifacts among "
                  f"{args.artifacts}", file=sys.stderr)
            raise SystemExit(2)
        report = perf.evaluate_rounds(
            rounds,
            noise_band=(args.noise if args.noise is not None
                        else perf.DEFAULT_NOISE_BAND),
        )
        if args.json:
            print(json.dumps(report, indent=1))
        else:
            print(perf.render_report(report))
        raise SystemExit(0 if report["ok"] else 1)

    # audit: zero-extra-compile discipline does not apply here — this IS
    # the command that compiles (tiny) programs, on purpose, to judge
    # the analytic model on the current backend.
    import jax
    import numpy as np

    from distributed_machine_learning_tpu.models import build_model
    from distributed_machine_learning_tpu.ops.flops import (
        device_peak_flops,
        forward_flops,
    )

    families = args.families or ["mlp", "simple_transformer",
                                 "transformer"]
    tol = (args.tolerance if args.tolerance is not None
           else perf.DEFAULT_CROSSCHECK_TOL)
    batch, seq, feats = 8, 16, 4
    rows = []
    ok = True
    for family in families:
        config = {"model": family, "dropout": 0.0}
        x = np.zeros((batch, seq, feats), np.float32)
        if family == "mlp":
            x = x.reshape(batch, seq * feats)
        model = build_model(config)
        variables = model.init(jax.random.key(0), x)

        def apply(v, xin):
            return model.apply(v, xin, deterministic=True)

        compiled = jax.jit(apply).lower(variables, x).compile()
        cost = perf.extract_cost(compiled)
        analytic = forward_flops(config, batch, seq, feats)
        finding = perf.crosscheck(
            analytic, (cost or {}).get("flops"), tolerance=tol,
            label=family,
        )
        dev = jax.devices()[0]
        row = {
            "family": family,
            "analytic_flops": analytic,
            "measured_flops": (cost or {}).get("flops"),
            "ratio": (
                round(cost["flops"] / analytic, 4)
                if cost and cost.get("flops") and analytic else None
            ),
            "roofline": perf.roofline(
                cost,
                device_peak_flops(dev),
                perf.device_hbm_bandwidth(dev),
            ),
            "divergence": finding,
        }
        rows.append(row)
        if finding is not None:
            ok = False
    if args.json:
        print(json.dumps({"tolerance": tol, "programs": rows, "ok": ok},
                         indent=1))
    else:
        for r in rows:
            ratio = f"{r['ratio']:.2f}x" if r["ratio"] else "n/a"
            verdict = (
                f"DIVERGENT ({r['divergence']['kind']})"
                if r["divergence"] else "ok"
            )
            bound = (r["roofline"] or {}).get("bound") or "?"
            print(f"[{r['family']}] measured/analytic {ratio} "
                  f"({verdict}); roofline: {bound}-bound")
    raise SystemExit(0 if ok else 1)


def _export_bundle(rest) -> None:
    import argparse

    p = argparse.ArgumentParser(prog="export-bundle")
    p.add_argument("experiment_dir",
                   help="an experiment directory (<storage_path>/<name>)")
    p.add_argument("out_dir", help="bundle directory to create")
    p.add_argument("--metric", default=None,
                   help="objective (default: recorded in "
                        "experiment_state.json)")
    p.add_argument("--mode", default=None, choices=("min", "max"))
    p.add_argument("--trial", default=None,
                   help="serve a specific trial instead of the best")
    p.add_argument("--precision", default="f32",
                   choices=("f32", "bf16", "int8"),
                   help="stored weight dtype (quant/); bf16/int8 require "
                        "--calibration")
    p.add_argument("--calibration", default=None,
                   help="path to a .npy calibration batch (n, features...) "
                        "— quantized exports measure their quality delta "
                        "on it")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu.serve import export_bundle

    calibration = None
    if args.calibration:
        import numpy as np

        calibration = np.load(args.calibration)
    try:
        out = export_bundle(
            args.experiment_dir, args.out_dir,
            metric=args.metric, mode=args.mode, trial_id=args.trial,
            precision=args.precision, calibration_batch=calibration,
        )
    except (FileNotFoundError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        raise SystemExit(1) from None
    note = f" [{args.precision}]" if args.precision != "f32" else ""
    print(f"exported best trial of {args.experiment_dir} -> {out}{note}")


def _loop(rest) -> None:
    """Self-healing loop status: the journal's episode/state/history plus
    the controller counters from an adjacent experiment_state.json —
    stdlib-only (readable from any host, no jax import)."""
    import argparse
    import json as _json
    import os as _os

    p = argparse.ArgumentParser(
        prog="loop",
        description="inspect a self-healing loop's journal (loop/)",
    )
    p.add_argument("action", choices=("status",))
    p.add_argument("path",
                   help="the journal file, or a loop out_dir containing "
                        "loop.json")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(rest)

    path = args.path
    if _os.path.isdir(path):
        path = _os.path.join(path, "loop.json")
    try:
        with open(path) as f:
            doc = _json.load(f)
    except (OSError, ValueError) as exc:
        print(f"error: cannot read journal {path}: {exc}", file=sys.stderr)
        raise SystemExit(1) from None
    state_path = _os.path.join(_os.path.dirname(path),
                               "experiment_state.json")
    counters = None
    try:
        with open(state_path) as f:
            counters = _json.load(f).get("loop")
    except (OSError, ValueError):
        pass
    if args.as_json:
        print(_json.dumps({"journal": doc, "counters": counters},
                          indent=2))
        return
    from distributed_machine_learning_tpu.loop.journal import (
        TERMINAL_STATES,
    )

    state = doc.get("state")
    open_note = (
        "" if state is None or state in TERMINAL_STATES
        else "  [OPEN - a controller should resume() this]"
    )
    print(f"episode {doc.get('episode', 0)}: "
          f"{state or 'never triggered'}{open_note}")
    if doc.get("trace_id"):
        print(f"trace_id: {doc['trace_id']}")
    print(f"completed episodes: {doc.get('completed_episodes', 0)} "
          f"(promotions: {doc.get('promotions', 0)}, "
          f"rollbacks: {doc.get('rollbacks', 0)})")
    history = doc.get("history", [])
    if history:
        print("history:")
        t0 = history[0].get("at_unix")
        for h in history:
            dt = (f"+{h['at_unix'] - t0:.2f}s"
                  if t0 and h.get("at_unix") else "")
            detail = {k: v for k, v in h.items()
                      if k not in ("state", "at_unix")
                      and isinstance(v, (str, int, float, bool))}
            tail = ("  " + ", ".join(
                f"{k}={v}" for k, v in sorted(detail.items())
            )) if detail else ""
            print(f"  {dt:>9}  {h.get('state')}{tail}")
    if counters:
        print("controller counters: " + ", ".join(
            f"{k}={counters[k]}" for k in (
                "episodes", "promotions", "rollbacks", "resumes",
                "gate_rejects", "aborts",
            ) if k in counters
        ))


def _journal(rest) -> None:
    """Durable-control-plane status: the head's write-ahead decision
    journal for an experiment (tune/journal.py) — committed or left open by
    a crashed head, decision count, head incarnations/replays, per-trial
    report watermarks.  Stdlib-only (readable from any host, no jax
    import); docs/operations.md 'Head crash recovery' is the runbook."""
    import argparse
    import json as _json
    import os as _os

    p = argparse.ArgumentParser(
        prog="journal",
        description="inspect an experiment's head decision journal "
                    "(tune/journal.py)",
    )
    p.add_argument("action", choices=("status",))
    p.add_argument("path",
                   help="the experiment directory (containing "
                        "journal.jsonl), or the journal file itself")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(rest)

    from distributed_machine_learning_tpu.tune.journal import (
        FILENAME,
        journal_status,
    )

    root = args.path
    if _os.path.basename(root) == FILENAME:
        root = _os.path.dirname(root) or "."
    status = journal_status(root)
    if args.as_json:
        print(_json.dumps(status, indent=2))
        return
    if not status["present"]:
        print(f"no journal at {_os.path.join(root, FILENAME)}")
        raise SystemExit(1)
    state = (
        "committed (experiment ended cleanly)" if status["committed"]
        else "OPEN — head died mid-sweep; resume with resume=\"auto\""
    )
    print(f"journal {status['path']}: {state}")
    print(f"decisions: {status['decisions']} "
          f"({status['records']} records, next trial index "
          f"{status['next_index']})")
    print(f"head incarnations: {status['head_starts']} "
          f"(journal replays: {status['replays']})")
    if status.get("trace_id"):
        print(f"trace_id: {status['trace_id']}")
    trials = status.get("trials") or {}
    if trials:
        print("trials:")
        for tid in sorted(trials):
            t = trials[tid]
            print(f"  {tid}: reported through iteration "
                  f"{t['reported_through']}, last decision "
                  f"{t['decision_at_watermark'] or '-'}"
                  + (f", terminal {t['status']}" if t.get("status")
                     else ""))
    if status.get("last_record"):
        print(f"last record: {status['last_record']}")


def _store(rest) -> None:
    """Content-store operator surface (store/): dedup stats, blob
    integrity verification, and reachability GC — the runbook commands
    behind docs/operations.md's store rows.  GC is a DRY RUN unless
    --run is given: it reports what the sweep would collect without
    deleting anything."""
    import argparse
    import json as _json
    import os as _os

    p = argparse.ArgumentParser(
        prog="store",
        description="inspect / verify / garbage-collect a content-"
                    "addressed store (store/)",
    )
    p.add_argument("action", choices=("stats", "verify", "gc"))
    p.add_argument("path",
                   help="the store root (a .cas directory), or any "
                        "directory it serves — an experiment or "
                        "checkpoint dir resolves to its .cas sibling "
                        "exactly the way writers do")
    p.add_argument("--run", action="store_true",
                   help="gc: actually delete unreachable blobs "
                        "(default is a dry run)")
    p.add_argument("--dry-run", action="store_true",
                   help="gc: report-only sweep (the default; explicit "
                        "spelling for scripts)")
    p.add_argument("--min-age-s", type=float, default=0.0,
                   help="gc: retain blobs younger than this many "
                        "seconds regardless of reachability (guards "
                        "cross-process writers beyond the pin table)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    args = p.parse_args(rest)
    if args.run and args.dry_run:
        p.error("--run and --dry-run are mutually exclusive")

    from distributed_machine_learning_tpu import store as store_lib

    root = args.path
    if (
        _os.path.basename(root.rstrip("/")) != store_lib.STORE_DIR_NAME
        and not _os.path.isdir(_os.path.join(root, store_lib.BLOBS_DIR))
    ):
        root = store_lib.store_root_for(_os.path.join(root, "_"))
    cas = store_lib.get_store(root)

    if args.action == "stats":
        out = cas.stats()
        if args.as_json:
            print(_json.dumps(out, indent=2, sort_keys=True))
            return
        print(f"store {out['root']}: {out['blobs']} blob(s), "
              f"{out['refs']} ref(s), {out['physical_bytes']} "
              f"physical byte(s)")
        c = out["counters"]
        print(f"this process: {c.get('puts', 0)} put(s), "
              f"{c.get('dedup_hits', 0)} dedup hit(s), "
              f"{c.get('bytes_logical', 0)} logical -> "
              f"{c.get('bytes_physical', 0)} physical byte(s) "
              f"(ratio {out['dedup_ratio']})")
    elif args.action == "verify":
        out = cas.verify()
        out["root"] = cas.root
        if args.as_json:
            print(_json.dumps(out, indent=2, sort_keys=True))
        else:
            print(f"store {cas.root}: {out['blobs']} blob(s) checked, "
                  f"{len(out['corrupt'])} corrupt")
            for digest in out["corrupt"]:
                print(f"  corrupt: {digest}")
        if out["corrupt"]:
            raise SystemExit(1)
    else:
        out = cas.gc(dry_run=not args.run, min_age_s=args.min_age_s)
        out["root"] = cas.root
        if args.as_json:
            print(_json.dumps(out, indent=2, sort_keys=True))
            return
        verb = "collected" if args.run else "would collect"
        print(f"store {cas.root}: {verb} {out['collected']} blob(s) "
              f"({out['reclaimed_bytes']} byte(s)), retained "
              f"{out['retained']}; {out['refs']} ref(s), "
              f"{out['broken_refs']} broken")
        if not args.run:
            print("dry run — pass --run to delete")


def _serve(rest) -> None:
    import argparse
    import time

    p = argparse.ArgumentParser(prog="serve")
    p.add_argument("--bundle", required=True,
                   help="a bundle directory (export-bundle's output)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--replicas", type=int, default=2,
                   help="initial replica count")
    p.add_argument("--max-batch-size", type=int, default=64)
    p.add_argument("--max-latency-ms", type=float, default=5.0,
                   help="micro-batcher flush deadline (--batcher micro)")
    p.add_argument("--max-bucket", type=int, default=256,
                   help="largest padded batch program (power-of-two grid)")
    p.add_argument("--batcher", choices=("continuous", "micro"),
                   default="continuous",
                   help="continuous = inflight, depth-adaptive flushes "
                        "(default); micro = size-or-latency")
    p.add_argument("--max-queue", type=int, default=1024,
                   help="bounded per-replica request queue; a full queue "
                        "answers 429 + Retry-After")
    p.add_argument("--target-step-ms", type=float, default=None,
                   help="latency budget per flush: the continuous batcher "
                        "steps its batch cap down the bucket grid while "
                        "the measured step time exceeds this")
    p.add_argument("--shed-watermark", type=int, default=None,
                   help="total queued requests past which admission "
                        "control sheds with 429 (default: off)")
    p.add_argument("--min-replicas", type=int, default=None,
                   help="autoscaler floor (default: --replicas)")
    p.add_argument("--max-replicas", type=int, default=None,
                   help="autoscaler ceiling; > --min-replicas enables the "
                        "autoscaler (default: off)")
    p.add_argument("--slo-p99-ms", type=float, default=None,
                   help="autoscaler scale-up trigger on windowed p99")
    p.add_argument("--autoscale-interval-s", type=float, default=0.5)
    p.add_argument("--tb-logdir", default=None,
                   help="stream /metrics scalars to a TensorBoard run dir")
    p.add_argument("--warmup-shape", default=None,
                   help="comma-separated per-row input shape (e.g. "
                        "'50,10' for seq x features) to pre-compile every "
                        "batch bucket before accepting traffic")
    p.add_argument("--gang", type=int, default=None,
                   help="pod-scale serving: each replica is a gang of N "
                        "member processes over a TP-spanning mesh "
                        "(serve/gang.py); the bundle is resharded onto "
                        "the gang's serving mesh at load")
    p.add_argument("--gang-devices", type=int, default=1,
                   help="local devices per gang member (with --gang)")
    args = p.parse_args(rest)

    import numpy as np

    from distributed_machine_learning_tpu.serve import (
        PredictionServer,
        load_bundle,
    )

    try:
        bundle = load_bundle(args.bundle)
    except (FileNotFoundError, ValueError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        raise SystemExit(1) from None
    autoscale = None
    lo = args.min_replicas if args.min_replicas is not None else args.replicas
    hi = args.max_replicas if args.max_replicas is not None else args.replicas
    if hi > lo:
        from distributed_machine_learning_tpu.serve import AutoscaleConfig

        autoscale = AutoscaleConfig(
            min_replicas=lo, max_replicas=hi,
            slo_p99_ms=args.slo_p99_ms,
            interval_s=args.autoscale_interval_s,
        )
    replica_factory = None
    if args.gang:
        from distributed_machine_learning_tpu.serve import (
            make_gang_replica_factory,
        )

        replica_factory = make_gang_replica_factory(
            processes=args.gang, local_devices=args.gang_devices,
        )
        # Source -> target topology at startup: the manifest records the
        # TRAINING topology (mesh shape, process count, rule fingerprint),
        # so the operator sees reshard-vs-direct before the first request.
        print(json.dumps({
            "gang_serving": {
                "source_topology": bundle.source_topology,
                "target_topology": {
                    "process_count": args.gang,
                    "local_device_counts": (
                        [args.gang_devices] * args.gang
                    ),
                },
            },
        }), flush=True)
    server = PredictionServer(
        bundle,
        host=args.host,
        port=args.port,
        num_replicas=args.replicas,
        max_batch_size=args.max_batch_size,
        max_latency_ms=args.max_latency_ms,
        max_bucket=args.max_bucket,
        batcher=args.batcher,
        max_queue=args.max_queue,
        target_step_ms=args.target_step_ms,
        shed_watermark=args.shed_watermark,
        autoscale=autoscale,
        tb_logdir=args.tb_logdir,
        replica_factory=replica_factory,
    )
    if args.warmup_shape:
        dims = tuple(
            int(d) for d in args.warmup_shape.split(",") if d.strip()
        )
        stats = server.warmup(np.zeros((1, *dims), np.float32))
        print(json.dumps({"warmup": stats}))
    host, port = server.start()
    print(json.dumps({
        "serving": f"http://{host}:{port}",
        "model_family": bundle.model_family,
        # Always printed (satellite of the quant/ PR): a mixed fleet's
        # logs say which dtype each process answers in.
        "precision": bundle.precision,
        "quality_delta_mape": bundle.quality_delta_mape,
        "replicas": args.replicas,
        "gang": args.gang,
        "batcher": args.batcher,
        "autoscale": (
            {"min": lo, "max": hi} if autoscale is not None else None
        ),
        "endpoints": ["/predict", "/healthz", "/metrics", "/admin/swap"],
    }), flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("shutting down", file=sys.stderr)
    finally:
        server.close()


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    usage = (
        "usage: python -m distributed_machine_learning_tpu "
        "{worker|info|probe|analyze|lint|audit-sharding|perf|trace|serve|"
        "loop|journal|store|export-bundle|export-orbax} [args]\n"
        "  worker         host trial supervisor (see 'worker --help')\n"
        "  lint           dmlint static analysis over the package (or given\n"
        "                 paths); exit 1 on any unsuppressed finding\n"
        "                 (--changed for pre-commit, --format=sarif for CI,\n"
        "                 --jax for the program-level jaxlint tier)\n"
        "  audit-sharding program-level sharding/donation audit (the jax\n"
        "                 tier + per-family partition coverage reports)\n"
        "  perf           compare: bench-round regression sentinel over\n"
        "                 BENCH_r*/MULTICHIP_r* artifacts (comparability\n"
        "                 classes; exit 1 on an in-class regression);\n"
        "                 audit: XLA cost-model vs analytic FLOPs\n"
        "  info           jax backend/device summary for this process\n"
        "  probe          bounded accelerator health check (child process)\n"
        "  analyze        <experiment_dir>: best config + trial table of a\n"
        "                 finished/interrupted experiment (--json for tools)\n"
        "  trace          export/merge/summarize structured traces from a\n"
        "                 traced run (tune.run(trace=True)): Chrome-trace/\n"
        "                 Perfetto JSON + per-phase wall-clock breakdowns\n"
        "  export-bundle  <experiment_dir> <out_dir>: freeze the best\n"
        "                 trial into a servable bundle (serve/export.py)\n"
        "  serve          --bundle <dir>: HTTP prediction service over\n"
        "                 compiled replicas (/predict /healthz /metrics)\n"
        "  loop           status <journal|out_dir>: a self-healing loop's\n"
        "                 episode state, history, and counters (loop/)\n"
        "  journal        status <experiment_dir>: the head's write-ahead\n"
        "                 decision journal — committed vs crash-open,\n"
        "                 incarnations, per-trial report watermarks\n"
        "  store          {stats|verify|gc} <root>: content-addressed\n"
        "                 store surface (store/) — dedup stats, blob\n"
        "                 integrity, reachability GC (gc is a dry run\n"
        "                 unless --run)\n"
        "  export-orbax   <ckpt.msgpack> <out_dir>: framework checkpoint\n"
        "                 -> orbax StandardCheckpoint"
    )
    if not argv or argv[0] in ("-h", "--help"):
        print(usage)
        return
    cmd, rest = argv[0], argv[1:]
    if cmd == "worker":
        from distributed_machine_learning_tpu.tune.cluster import _main

        _main(rest)
    elif cmd == "info":
        _info()
    elif cmd == "probe":
        _probe(rest)
    elif cmd == "analyze":
        _analyze(rest)
    elif cmd == "lint":
        _lint(rest)
    elif cmd == "audit-sharding":
        _audit_sharding(rest)
    elif cmd == "perf":
        _perf(rest)
    elif cmd == "trace":
        _trace(rest)
    elif cmd == "serve":
        _serve(rest)
    elif cmd == "loop":
        _loop(rest)
    elif cmd == "journal":
        _journal(rest)
    elif cmd == "store":
        _store(rest)
    elif cmd == "export-bundle":
        _export_bundle(rest)
    elif cmd == "export-orbax":
        if len(rest) != 2:
            print(usage, file=sys.stderr)
            raise SystemExit(2)
        from distributed_machine_learning_tpu.tune.checkpoint import (
            export_orbax,
        )

        try:
            out = export_orbax(rest[0], rest[1])
        except ImportError:
            print("error: orbax-checkpoint is not installed "
                  "(pip install 'distributed-machine-learning-tpu[orbax]')",
                  file=sys.stderr)
            raise SystemExit(1) from None
        except (FileNotFoundError, ValueError) as exc:
            # The predictable misuses (missing checkpoint, out_dir already
            # exists) get a one-liner, not a stack dump.
            print(f"error: {exc}", file=sys.stderr)
            raise SystemExit(1) from None
        print(f"exported {rest[0]} -> {out}")
    else:
        print(usage, file=sys.stderr)
        raise SystemExit(2)


if __name__ == "__main__":
    main()

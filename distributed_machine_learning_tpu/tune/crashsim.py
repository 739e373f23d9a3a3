"""Head-crash simulation harness: SIGKILL the driver mid-sweep, resume.

The chaos plane can kill the head at an exact decision number
(``chaos.kill_head_at`` — the ``os._exit(86)`` fires right after the
decision record is fsync'd and BEFORE its effect happens), but a dead
head takes its test process with it.  This module runs the sweep in a
CHILD process so the kill is survivable and measurable:

* :func:`run_child` — execute one sweep (thread or cluster driver) in a
  subprocess built from a JSON spec; the child writes its result
  (best trial, counters, per-trial iteration streams) to a file, so a
  crashed child leaves no result and a clean child leaves exactly one.
* :func:`killed_then_resumed` — the full scenario: sweep killed at
  decision N (exit 86, or 87 for a torn journal append), uncommitted
  journal detected, ``resume="auto"`` child finishes the experiment.
  Returns the resumed result plus the recovery timings the bench
  ``head_recovery`` section reports (detect / replay / requeue seconds,
  all derived from journal record timestamps — no harness clocks inside
  the measured path).
* :func:`control_run` — the same spec uninterrupted, for
  crashed-equals-control assertions.
* :func:`suggestion_stream` — the journaled ``create`` stream
  ``[(trial_id, config), ...]``: the object restart-determinism tests
  compare between a killed+resumed sweep and its control.

Used by tests/test_head_crash.py, scripts/lint_gate.py's head-crash
smoke, and bench.py.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from distributed_machine_learning_tpu.tune import journal as journal_lib

TRAINABLE_REF = "distributed_machine_learning_tpu.tune.crashsim:crashsim_trainable"

#: exit codes the chaos plane uses for an injected head death
HEAD_KILL_EXIT = 86
TORN_JOURNAL_EXIT = 87


def crashsim_trainable(config):
    """Deterministic checkpointing trainable: score depends only on
    ``config['x']`` and the epoch, so a requeued re-run reports the
    exact values the killed run would have."""
    from distributed_machine_learning_tpu import tune

    ckpt = tune.get_checkpoint()
    start = int(ckpt["epoch"]) + 1 if ckpt else 1
    epochs = int(config.get("epochs", 5))
    for epoch in range(start, epochs + 1):
        time.sleep(float(config.get("epoch_s", 0.01)))
        score = (float(config["x"]) - 0.7) ** 2 + 0.1 / epoch
        tune.report(
            {"score": score, "training_iteration": epoch},
            checkpoint={"epoch": epoch},
        )


def _build_searcher(kind: Optional[str], seed: int):
    if not kind:
        return None
    from distributed_machine_learning_tpu import tune

    if kind == "bayes":
        return tune.BayesOptSearch(random_search_steps=4)
    raise ValueError(f"unknown crashsim searcher {kind!r}")


def _build_scheduler(kind: Optional[str], seed: int):
    if not kind:
        return None
    from distributed_machine_learning_tpu.tune import schedulers

    if kind == "asha":
        return schedulers.ASHAScheduler(
            max_t=8, grace_period=2, reduction_factor=2
        )
    if kind == "pbt":
        from distributed_machine_learning_tpu import tune

        return schedulers.PopulationBasedTraining(
            perturbation_interval=2,
            hyperparam_mutations={"x": tune.uniform(0.0, 1.0)},
            quantile_fraction=0.5,
            seed=seed,
        )
    raise ValueError(f"unknown crashsim scheduler {kind!r}")


def _child_main(spec_path: str) -> int:
    """Run ONE sweep per the JSON spec and write the result file.

    This IS the head process: an env-activated ``kill_head_at`` plan
    ``os._exit(86)``s it mid-journal-append, exactly like an OOM-kill."""
    from distributed_machine_learning_tpu import chaos, tune

    chaos.activate_from_env()
    with open(spec_path) as f:
        spec = json.load(f)

    space = {
        "x": tune.uniform(0.0, 1.0),
        "epochs": int(spec.get("epochs", 5)),
        "epoch_s": float(spec.get("epoch_s", 0.01)),
    }
    seed = int(spec.get("seed", 7))
    common = dict(
        metric=spec.get("metric", "score"),
        mode=spec.get("mode", "min"),
        num_samples=int(spec.get("num_samples", 6)),
        scheduler=_build_scheduler(spec.get("scheduler"), seed),
        search_alg=_build_searcher(spec.get("searcher"), seed),
        storage_path=spec["storage_path"],
        name=spec["name"],
        seed=seed,
        verbose=0,
        resume=spec.get("resume", False),
        trace=bool(spec.get("trace", False)),
    )
    if spec.get("driver") == "cluster":
        from distributed_machine_learning_tpu.tune import cluster

        analysis = cluster.run_distributed(
            TRAINABLE_REF,
            space,
            workers=spec["workers"],
            checkpoint_storage=spec.get("checkpoint_storage"),
            **common,
        )
    else:
        analysis = tune.run(
            crashsim_trainable,
            space,
            max_concurrent=spec.get("max_concurrent"),
            **common,
        )

    best = analysis.best_trial
    out = {
        "best_trial": best.trial_id if best else None,
        "best_config": dict(best.config) if best else None,
        "best_score": analysis.best_result.get(common["metric"])
        if best else None,
        "num_terminated": analysis.num_terminated(),
        "trial_iterations": {
            t.trial_id: [
                int(r.get("training_iteration", 0)) for r in t.results
            ]
            for t in analysis.trials
        },
    }
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=2)
    os.replace(tmp, spec["out"])
    return 0


def _child_env(chaos_plan: Optional[Dict[str, Any]]) -> Dict[str, str]:
    # The child is CPU-only; drop any chaos plan inherited from the
    # calling process.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DML_CHAOS_PLAN", None)
    if chaos_plan is not None:
        env["DML_CHAOS_PLAN"] = json.dumps(chaos_plan)
    return env


def run_child(
    spec: Dict[str, Any],
    chaos_plan: Optional[Dict[str, Any]] = None,
    timeout: float = 300.0,
) -> Tuple[int, Optional[Dict[str, Any]]]:
    """Run one sweep in a subprocess; returns ``(returncode, result)``.

    ``result`` is the child's output document, or None when the child
    died before writing it (the crash phase of the scenario)."""
    spec = dict(spec)
    root = spec["storage_path"]
    os.makedirs(root, exist_ok=True)
    spec.setdefault("out", os.path.join(
        root, f"{spec['name']}_result_{spec.get('phase', 'run')}.json"
    ))
    fd, spec_path = tempfile.mkstemp(suffix=".json", dir=root)
    with os.fdopen(fd, "w") as f:
        json.dump(spec, f)
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "distributed_machine_learning_tpu.tune.crashsim", spec_path],
            env=_child_env(chaos_plan),
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    finally:
        try:
            os.unlink(spec_path)
        except OSError:
            pass
    result = None
    if os.path.exists(spec["out"]):
        with open(spec["out"]) as f:
            result = json.load(f)
        os.unlink(spec["out"])
    if proc.returncode not in (0, HEAD_KILL_EXIT, TORN_JOURNAL_EXIT):
        raise RuntimeError(
            f"crashsim child rc={proc.returncode}\n"
            f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
        )
    return proc.returncode, result


def _recovery_timings(root: str) -> Dict[str, float]:
    """Replay/requeue durations from journal record timestamps: the
    resumed head's ``head_start`` → ``replay`` gap is the replay, the
    ``replay`` → first ``dispatch`` gap is the requeue."""
    records = journal_lib.read_records(root)
    head2 = replay_rec = first_dispatch = None
    for rec in records:
        if rec.get("type") == "head_start" and int(
            rec.get("incarnation", 1)
        ) >= 2 and head2 is None:
            head2 = rec
        elif head2 is not None and rec.get("type") == "replay" and (
            replay_rec is None
        ):
            replay_rec = rec
        elif replay_rec is not None and rec.get("type") == "dispatch" and (
            first_dispatch is None
        ):
            first_dispatch = rec
    out = {"replay_s": 0.0, "requeue_s": 0.0}
    if head2 and replay_rec:
        out["replay_s"] = round(
            float(replay_rec["at_unix"]) - float(head2["at_unix"]), 4
        )
    if replay_rec and first_dispatch:
        out["requeue_s"] = round(
            float(first_dispatch["at_unix"]) - float(replay_rec["at_unix"]), 4
        )
    return out


def killed_then_resumed(
    storage_path: str,
    name: str,
    *,
    driver: str = "thread",
    kill_at: int = 6,
    torn_write: bool = False,
    workers: Optional[List[str]] = None,
    checkpoint_storage: Optional[str] = None,
    searcher: Optional[str] = None,
    scheduler: Optional[str] = None,
    num_samples: int = 6,
    epochs: int = 5,
    seed: int = 7,
    max_concurrent: Optional[int] = None,
    trace: bool = False,
    timeout: float = 300.0,
) -> Dict[str, Any]:
    """Kill the head at decision ``kill_at``, auto-resume, report.

    Returns ``{crash_rc, detect_s, replay_s, requeue_s, resume_total_s,
    result, journal}`` where ``result`` is the RESUMED child's output
    and ``journal`` is :func:`tune.journal.journal_status` afterwards.
    """
    spec = {
        "driver": driver,
        "storage_path": storage_path,
        "name": name,
        "workers": workers,
        "checkpoint_storage": checkpoint_storage,
        "searcher": searcher,
        "scheduler": scheduler,
        "num_samples": num_samples,
        "epochs": epochs,
        "seed": seed,
        "max_concurrent": max_concurrent,
        "trace": trace,
    }
    plan_key = (
        "kill_head_during_journal_write" if torn_write else "kill_head_at"
    )
    rc, _ = run_child(
        {**spec, "phase": "crash"},
        chaos_plan={plan_key: kill_at},
        timeout=timeout,
    )
    expected = TORN_JOURNAL_EXIT if torn_write else HEAD_KILL_EXIT
    if rc != expected:
        raise RuntimeError(
            f"crash phase exited {rc}, expected {expected} "
            f"(plan {plan_key}={kill_at})"
        )

    root = os.path.join(storage_path, name)
    t0 = time.monotonic()
    uncommitted = journal_lib.is_uncommitted(root)
    detect_s = round(time.monotonic() - t0, 4)
    if not uncommitted:
        raise RuntimeError("killed head left a committed journal")

    t1 = time.monotonic()
    rc2, result = run_child(
        {**spec, "phase": "resume", "resume": "auto"}, timeout=timeout
    )
    resume_total_s = round(time.monotonic() - t1, 4)
    if rc2 != 0 or result is None:
        raise RuntimeError(f"resume phase exited {rc2} without a result")

    return {
        "crash_rc": rc,
        "detect_s": detect_s,
        "resume_total_s": resume_total_s,
        **_recovery_timings(root),
        "result": result,
        "journal": journal_lib.journal_status(root),
    }


def control_run(
    storage_path: str,
    name: str,
    *,
    driver: str = "thread",
    workers: Optional[List[str]] = None,
    checkpoint_storage: Optional[str] = None,
    searcher: Optional[str] = None,
    scheduler: Optional[str] = None,
    num_samples: int = 6,
    epochs: int = 5,
    seed: int = 7,
    max_concurrent: Optional[int] = None,
    trace: bool = False,
    timeout: float = 300.0,
) -> Dict[str, Any]:
    """The uninterrupted twin of :func:`killed_then_resumed`."""
    rc, result = run_child(
        {
            "driver": driver,
            "storage_path": storage_path,
            "name": name,
            "workers": workers,
            "checkpoint_storage": checkpoint_storage,
            "searcher": searcher,
            "scheduler": scheduler,
            "num_samples": num_samples,
            "epochs": epochs,
            "seed": seed,
            "max_concurrent": max_concurrent,
            "trace": trace,
            "phase": "control",
        },
        timeout=timeout,
    )
    if rc != 0 or result is None:
        raise RuntimeError(f"control run exited {rc} without a result")
    return result


def suggestion_stream(root: str) -> List[Tuple[str, Dict[str, Any]]]:
    """The journaled searcher output: ``(trial_id, config)`` per
    ``create`` decision, in journal order."""
    return [
        (rec["trial_id"], rec["config"])
        for rec in journal_lib.read_records(root)
        if rec.get("type") == "create"
    ]


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(_child_main(sys.argv[1]))

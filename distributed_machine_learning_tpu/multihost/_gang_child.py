"""Gang-member child: one process of a trial's process-spanning mesh.

Spawned by a worker supervisor (``multihost/spawn.py``) with its
:class:`~distributed_machine_learning_tpu.multihost.bootstrap.GangSpec`
in the environment.  Joins the gang's ``jax.distributed`` runtime BEFORE
any backend use, gates on the all-joined barrier, then runs the trainable
under an SPMD-aware session:

* **Only the coordinator (gang process 0) reports.**  Its ``report``
  sends the result frame up the control plane and blocks on the head's
  decision; every OTHER member's ``report`` joins a
  ``broadcast_from_coordinator`` of that decision instead — so all N
  processes take the same continue/stop/pause branch without a side
  channel, and the head sees exactly one metric stream per trial.
* **Every member checkpoints.**  A process-spanning pytree can only be
  saved by all its owners (``ckpt/format.py`` writes per-process chunks;
  process 0 writes the index/COMMIT after the all-chunks barrier), so the
  save happens HERE on every process before the coordinator's result
  frame names the generation.
* **Chaos reaches gangs.**  ``DML_CHAOS_PLAN`` rides the spawn env;
  ``kill_process_at`` hard-exits THIS member at its scheduled report
  boundary — the mid-collective member death the gang teardown path
  exists for.
"""

from __future__ import annotations

import os
import sys
import traceback

from distributed_machine_learning_tpu.tune._process_child import (
    read_frame,
    write_frame,
)

DECISION_CODES = {"continue": 0, "stop": 1, "pause": 2}
DECISION_NAMES = {v: k for k, v in DECISION_CODES.items()}


class _TrialStub:
    def __init__(self, trial_id: str, config: dict):
        self.trial_id = trial_id
        self.config = config


def main() -> None:
    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    sys.stdout = sys.stderr  # user prints must not corrupt the frame stream

    try:
        init = read_frame(stdin)
    except EOFError:
        return  # parent died before dispatching

    try:
        from distributed_machine_learning_tpu import chaos
        from distributed_machine_learning_tpu.multihost.bootstrap import (
            GangSpec,
        )

        chaos.activate_from_env()
        spec = GangSpec.from_env()
        if spec is None:
            raise RuntimeError("gang child spawned without DML_GANG_SPEC")

        import jax

        # Decide from the ENV only — jax.default_backend() would
        # initialize the backend, which must not happen before
        # jax.distributed.initialize below.
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Cross-process CPU collectives need a backend; gloo ships in jaxlib.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

        from distributed_machine_learning_tpu import obs
        from distributed_machine_learning_tpu.compilecache import (
            enable_persistent_cache,
        )
        from distributed_machine_learning_tpu.multihost import (
            bootstrap,
            runtime,
        )

        obs.configure_from_frame(
            init.get("obs"),
            label=f"gang{spec.process_id}-{os.getpid()}",
        )
        # Join BEFORE the persistent-cache attach (which touches jax
        # config, not the backend) and before any device enumeration.
        described = bootstrap.join_gang(spec)
        enable_persistent_cache()
        write_frame(stdout, ("joined", described))

        import cloudpickle
        import numpy as np

        from distributed_machine_learning_tpu.tune import (
            checkpoint as ckpt_lib,
        )
        from distributed_machine_learning_tpu.tune.session import (
            PauseTrial,
            Session,
            StopTrial,
            set_session,
        )

        trainable = cloudpickle.loads(init["trainable"])
        trial_id = init["trial_id"]
        config = dict(init["config"])
        coordinator = runtime.is_coordinator()
        ckpt_dir = init.get("checkpoint_dir")
        ckpt_format = init.get("checkpoint_format", "sharded")
        iteration = [int(init.get("start_iteration", 0))]

        def _broadcast_decision(local: str) -> str:
            """All members leave with the coordinator's decision."""
            code = runtime.broadcast_from_coordinator(
                np.int32(DECISION_CODES.get(local, 0))
            )
            return DECISION_NAMES[int(code)]

        def report_fn(metrics, checkpoint) -> str:
            plan = chaos.active_plan()
            if plan is not None:
                # The gang fault class: ONE member hard-dies at a report
                # boundary; its peers are left mid-collective for the
                # teardown path to reap.
                plan.maybe_kill_process(
                    trial_id, iteration[0] + 1, spec.process_id,
                    incarnation=int(init.get("incarnation", 1)),
                )
                if coordinator:
                    plan.maybe_crash_trial(trial_id, iteration[0] + 1)
            iteration[0] += 1
            ckpt_path = None
            if checkpoint is not None and ckpt_dir:
                # Every member writes its shards; the format's internal
                # barriers order chunks before process 0's index/COMMIT.
                ckpt_path = ckpt_lib.checkpoint_path(
                    ckpt_dir, iteration[0], ckpt_format
                )
                ckpt_lib.save_checkpoint(ckpt_path, checkpoint)
            if coordinator:
                write_frame(
                    stdout, ("result", dict(metrics), ckpt_path)
                )
                msg = read_frame(stdin)
                assert msg[0] == "decision", msg
                return _broadcast_decision(msg[1])
            return _broadcast_decision("continue")

        import time as _time

        last_beat = [0.0]

        def heartbeat_fn() -> None:
            if not coordinator:
                return
            now = _time.monotonic()
            if now - last_beat[0] >= 0.05:
                last_beat[0] = now
                write_frame(stdout, ("beat",))

        restore_path = init.get("restore_path")

        def checkpoint_loader():
            if not restore_path:
                return None
            # Every member restores the SAME full host tree from shared
            # storage (the resharding restore's single-process side —
            # free, per ckpt/format.py); the trainable re-shards it onto
            # the live spanning mesh.
            tree, used, used_it = ckpt_lib.load_checkpoint_with_fallback(
                restore_path, ckpt_dir,
            )
            if used != restore_path and coordinator:
                print(
                    f"[gang] {trial_id}: restore fell back "
                    f"{restore_path} -> {used} (it={used_it})",
                    flush=True,
                )
            return tree

        set_session(Session(
            _TrialStub(trial_id, config),
            report_fn,
            checkpoint_loader,
            list(jax.devices()),
            heartbeat_fn=heartbeat_fn,
        ))
        try:
            with obs.span("trial", {
                "trial_id": trial_id,
                "incarnation": int(init.get("incarnation", 0)),
                "gang_id": spec.gang_id,
                "process_id": spec.process_id,
            }):
                trainable(config)
            obs.flush()  # BEFORE the terminal frame: the supervisor may
            write_frame(stdout, ("complete",))  # reap us right after it
        except (StopTrial, PauseTrial):
            obs.flush()
            write_frame(stdout, ("complete",))
        finally:
            set_session(None)
            obs.flush()
    except BaseException:  # noqa: BLE001 - everything goes to the parent
        try:
            write_frame(stdout, ("error", traceback.format_exc()))
        except (OSError, ValueError):
            pass


if __name__ == "__main__":
    main()

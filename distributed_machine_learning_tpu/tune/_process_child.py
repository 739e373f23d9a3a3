"""Child-process entry for the process-per-trial executor.

Run as ``python -m distributed_machine_learning_tpu.tune._process_child`` by
``ProcessTrialExecutor`` with the trial's device visibility already fixed in
the process environment (the TPU analogue of Ray setting
``CUDA_VISIBLE_DEVICES`` per trial actor, `ray-tune-hpo-regression.py:286`;
SURVEY.md §7 step 3).  Speaks a length-prefixed pickle protocol over binary
stdio:

    child  -> parent  ("warm",)            (pre-warmed child finished its
                                            imports; sent before any frame
                                            is read when DML_PREWARM=1)
    parent -> child   ("precompile", {"key", "trainable": bytes, "config",
                       "sys_path"})        (compile this program during
                                            scheduler think-time)
    child  -> parent  ("prewarmed", key, backend_compiles) |
                      ("prewarm_error", key, traceback_str)
    parent -> child   {"trial_id", "config", "trainable": bytes,
                       "restore": pytree|None, "sys_path": [...]}   (init)
    child  -> parent  ("result", metrics, ckpt_bytes|None)
    parent -> child   ("decision", "continue"|"stop"|"pause")
    child  -> parent  ("beat",)            (tune.heartbeat(); no reply)
    child  -> parent  ("complete",) | ("error", traceback_str)

**Pre-warmed mode** (``DML_PREWARM=1``): the executor spawns the child
BEFORE any trial is assigned; the child front-loads the slow part of trial
startup — jax import, device enumeration, persistent compile-cache attach —
and then blocks on stdin.  Dispatch-to-first-step latency collapses to
frame parsing + the trainable's own work.  A ``precompile`` frame goes one
step further: the child runs the trainable under a session that stops at
the FIRST report boundary, which traces and compiles every program the
trial would use (populating the shared persistent/AOT caches) while the
scheduler is still thinking.

The child's real stdout is reserved for frames; ``print`` inside trainables
is redirected to stderr so it can't corrupt the stream.
"""

from __future__ import annotations

import os
import pickle
import struct
import sys
import traceback

_LEN = struct.Struct(">Q")

PREWARM_ENV = "DML_PREWARM"


def read_frame(stream):
    header = stream.read(_LEN.size)
    if len(header) < _LEN.size:
        raise EOFError("frame stream closed")
    (n,) = _LEN.unpack(header)
    payload = stream.read(n)
    if len(payload) < n:
        raise EOFError("truncated frame")
    return pickle.loads(payload)


def write_frame(stream, obj) -> None:
    payload = pickle.dumps(obj)
    stream.write(_LEN.pack(len(payload)) + payload)
    stream.flush()


class _TrialStub:
    """Just enough of a Trial for Session users inside the child."""

    def __init__(self, trial_id: str, config: dict):
        self.trial_id = trial_id
        self.config = config


def _extend_sys_path(paths):
    for p in reversed(paths or []):
        if p not in sys.path:
            sys.path.insert(0, p)


class _StopAfterFirstReport(Exception):
    """Precompile sentinel: every program is compiled by the time the first
    report boundary is reached; nothing after it is compile work."""


def _run_precompile(msg, stdout) -> None:
    """Trace + compile the trial's programs without running the trial.

    Runs the trainable under a session whose report raises at the first
    boundary — by then the epoch/eval programs are compiled and sitting in
    the jit, persistent, and AOT caches for the REAL incarnation (this
    child or any sibling process) to hit."""
    key = msg.get("key", "")
    try:
        _extend_sys_path(msg.get("sys_path"))
        import cloudpickle
        import jax

        from distributed_machine_learning_tpu.compilecache import get_tracker
        from distributed_machine_learning_tpu.tune.session import (
            Session,
            set_session,
        )

        trainable = cloudpickle.loads(msg["trainable"])
        tracker = get_tracker()
        compiles_before = tracker.total_backend_compiles()

        def report_fn(_metrics, _checkpoint) -> str:
            raise _StopAfterFirstReport()

        config = dict(msg.get("config") or {})
        try:
            set_session(
                Session(
                    _TrialStub(f"prewarm-{key}", config),
                    report_fn,
                    lambda: None,
                    jax.devices(),
                )
            )
            trainable(config)
        except _StopAfterFirstReport:
            pass
        finally:
            set_session(None)
        write_frame(
            stdout,
            ("prewarmed", key,
             tracker.total_backend_compiles() - compiles_before),
        )
    except BaseException:  # noqa: BLE001 - report, keep serving
        write_frame(stdout, ("prewarm_error", key, traceback.format_exc()))


def main() -> None:
    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    sys.stdout = sys.stderr  # user prints must not corrupt the frame stream

    prewarmed = os.environ.get(PREWARM_ENV) == "1"
    if prewarmed:
        # Front-load the slow imports BEFORE any trial exists, then tell the
        # parent this runner is hot.  Import errors surface as an error
        # frame, exactly as they would on the cold path.
        try:
            import cloudpickle  # noqa: F401
            import jax  # noqa: F401

            from distributed_machine_learning_tpu.compilecache import (
                enable_persistent_cache,
                get_tracker,
            )

            enable_persistent_cache()
            get_tracker()  # install monitoring listeners pre-trial
            jax.devices()  # device enumeration is part of cold start
            write_frame(stdout, ("warm",))
        except BaseException:  # noqa: BLE001
            write_frame(stdout, ("error", traceback.format_exc()))
            return

    # Frame loop: precompile requests may arrive (and repeat) before the
    # init frame; the first init frame runs the trial and ends the process.
    while True:
        try:
            frame = read_frame(stdin)
        except EOFError:
            return  # pool teardown before any trial was assigned
        if isinstance(frame, tuple) and frame and frame[0] == "precompile":
            _run_precompile(frame[1], stdout)
            continue
        break

    init = frame
    # Everything from here on reports failures as frames: an unpicklable
    # trainable or a broken import must surface as the trial's error, not as
    # a silent child death.
    try:
        _extend_sys_path(init.get("sys_path", []))
        import cloudpickle

        trainable = cloudpickle.loads(init["trainable"])

        import jax

        from distributed_machine_learning_tpu import obs
        from distributed_machine_learning_tpu.tune.session import (
            PauseTrial,
            Session,
            StopTrial,
            set_session,
        )
        from distributed_machine_learning_tpu.compilecache import (
            get_tracker,
        )
        tracker = get_tracker()
        devices = jax.devices()
        # Join the driver's trace (same trace id; spans parent under the
        # driver's trial.dispatch span) and point flight dumps at the
        # experiment dir.  A SIGTERM — the runner's stall/time-limit kill
        # path — dumps this process's flight ring + open-span stacks
        # BEFORE dying, so a killed wedge leaves its hang site behind.
        obs.configure_from_frame(
            init.get("obs"), label=f"child{os.getpid()}"
        )

        import signal as _signal

        def _on_sigterm(_signum, _frame):
            obs.dump_flight_recorder(
                f"sigterm_{init.get('trial_id', 'trial')}"
            )
            obs.flush()
            os._exit(128 + _signal.SIGTERM)

        try:
            _signal.signal(_signal.SIGTERM, _on_sigterm)
        except (ValueError, OSError):
            # Not the main thread / unsupported platform: forensics are
            # then the parent's job, the trial itself is unaffected.
            pass
    except BaseException:  # noqa: BLE001
        write_frame(stdout, ("error", traceback.format_exc()))
        return

    def report_fn(metrics, checkpoint) -> str:
        metrics.setdefault("compile_time_s", round(tracker.thread_seconds(), 4))
        metrics.setdefault("compile_cache_hits", tracker.thread_cache_hits())
        ckpt_bytes = None
        if checkpoint is not None:
            ckpt_bytes = pickle.dumps(jax.device_get(checkpoint))
        write_frame(stdout, ("result", dict(metrics), ckpt_bytes))
        msg = read_frame(stdin)
        assert msg[0] == "decision", msg
        return msg[1]

    # Mid-epoch liveness: tune.heartbeat() in the trainable emits a "beat"
    # frame so the parent's watchdog sees progress between reports.  Rate-
    # limited host-side — a heartbeat in a hot step loop must not flood the
    # pipe.  Same thread as report_fn (the trainable's), so frame writes
    # never interleave.
    import time as _time

    last_beat = [0.0]

    def heartbeat_fn() -> None:
        now = _time.monotonic()
        if now - last_beat[0] >= 0.05:
            last_beat[0] = now
            write_frame(stdout, ("beat",))

    restore = init.get("restore")
    try:
        set_session(
            Session(
                _TrialStub(init["trial_id"], dict(init["config"])),
                report_fn,
                lambda: restore,
                devices,
                heartbeat_fn=heartbeat_fn,
            )
        )
        with obs.span(
            "trial",
            {"trial_id": init["trial_id"],
             "incarnation": int(init.get("incarnation", 0))},
        ):
            trainable(dict(init["config"]))
        write_frame(stdout, ("complete",))
    except (StopTrial, PauseTrial):
        write_frame(stdout, ("complete",))
    except BaseException:  # noqa: BLE001 - everything goes back to the parent
        write_frame(stdout, ("error", traceback.format_exc()))
    finally:
        obs.flush()


if __name__ == "__main__":
    main()

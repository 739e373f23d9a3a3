"""Trial executor: binds trials to TPU devices and runs them.

Native replacement for Ray's actor-per-trial resource scheduling (SURVEY.md
§2b D3): the reference leaned on Ray setting ``CUDA_VISIBLE_DEVICES`` so every
trial could hard-code ``cuda:0`` (`ray-tune-hpo-regression.py:286`).  Here a
``DeviceManager`` owns the enumerated ``jax.devices()`` of the slice and leases
1..N cores per trial; the trainable runs under ``jax.default_device`` (JAX
config contexts are thread-local) so its jit executables land on its leased
core without any process-env games.  Threads, not processes: JAX dispatch
releases the GIL while XLA executes, so N trials on N cores overlap compute;
compilation contention is bounded and amortized by the jit cache.

``report`` is synchronous with the runner (the thread blocks until the
scheduler answers), which makes early-stop decisions take effect on the very
next epoch and keeps scheduler state single-threaded.
"""

from __future__ import annotations

import os
import pickle
import queue
import re
import subprocess
import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax

from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.analysis.locks import named_lock
from distributed_machine_learning_tpu.ckpt import metrics as ckpt_metrics
from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
from distributed_machine_learning_tpu.tune.session import (
    PauseTrial,
    Session,
    StopTrial,
    set_session,
)
from distributed_machine_learning_tpu.tune.trial import Trial
from distributed_machine_learning_tpu.compilecache import (
    get_counters as get_compile_counters,
    get_tracker,
)


class DeviceManager:
    """Leases jax devices to trials. Thread-compatible (runner-thread only).

    Tracks per-device busy time so the runner can report chip utilization
    (the BASELINE.md ≥90%-utilization target needs to be measurable).
    """

    def __init__(self, devices: Optional[List] = None):
        self.devices = list(devices) if devices is not None else list(jax.devices())
        if not self.devices:
            raise RuntimeError("No jax devices available")
        self._free = list(range(len(self.devices)))
        self._busy_s = [0.0] * len(self.devices)
        self._leased_at: Dict[int, float] = {}

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def acquire(self, n: int) -> Optional[List]:
        if n > len(self.devices):
            raise ValueError(
                f"Trial requests {n} devices but only {len(self.devices)} exist"
            )
        if len(self._free) < n:
            return None
        idxs = self._pick_adjacent(n)
        for i in idxs:
            self._free.remove(i)
        now = time.time()
        for i in idxs:
            self._leased_at[i] = now
        return [(i, self.devices[i]) for i in idxs]

    def _pick_adjacent(self, n: int) -> List[int]:
        """Choose n free devices that are ICI-adjacent (SURVEY.md §7 step 9).

        A multi-device trial's collectives ride the ICI links between its
        cores; a lease of topologically scattered cores pays extra hops for
        every all-reduce.  Preference order:

        1. the free window of n *consecutive* device indices whose physical
           ``coords`` (when the platform exposes them) span the smallest
           bounding box — consecutive indices are ICI-adjacent on TPU
           (enumeration follows the torus), and the coords check breaks ties
           across wraparound boundaries;
        2. failing any full window, the n free indices with the tightest
           index span (fragmented pool).
        """
        free = sorted(self._free)
        if n == 1:
            return [free[0]]
        free_set = set(free)
        best_window, best_cost = None, None
        for start in free:
            window = list(range(start, start + n))
            if not all(i in free_set for i in window):
                continue
            cost = self._coords_span(window)
            if best_cost is None or cost < best_cost:
                best_window, best_cost = window, cost
        if best_window is not None:
            return best_window
        # No contiguous window free: take the tightest cluster of n indices.
        best, best_span = free[:n], free[n - 1] - free[0]
        for k in range(1, len(free) - n + 1):
            span = free[k + n - 1] - free[k]
            if span < best_span:
                best, best_span = free[k : k + n], span
        return list(best)

    def _coords_span(self, idxs: List[int]) -> float:
        """Bounding-box volume of the devices' physical coords (1.0 if the
        platform exposes no coords — all windows tie, index order wins)."""
        coords = []
        for i in idxs:
            c = getattr(self.devices[i], "coords", None)
            if c is None:
                return 1.0
            coords.append(tuple(c))
        span = 1.0
        for dim in range(len(coords[0])):
            vals = [c[dim] for c in coords]
            span *= max(vals) - min(vals) + 1
        return span

    def release(self, leased: List):
        now = time.time()
        for i, _ in leased:
            self._free.append(i)
            start = self._leased_at.pop(i, None)
            if start is not None:
                self._busy_s[i] += now - start
        self._free.sort()

    def utilization(self, wall_clock_s: float) -> float:
        """Fraction of device-seconds spent leased to trials over the run."""
        if wall_clock_s <= 0:
            return 0.0
        now = time.time()
        busy = sum(self._busy_s) + sum(
            now - start for start in self._leased_at.values()
        )
        return min(busy / (wall_clock_s * len(self.devices)), 1.0)


def _rewind_after_fallback(trial: Trial, tree, used_path, used_iteration):
    """Align a trial's progress bookkeeping with what actually restored.

    When corruption forced ``load_checkpoint_with_fallback`` off the
    requested restore target (older generation, or nothing at all), the
    trial's ``restore_base``/checkpoint pointers must rewind with it —
    otherwise ``training_iteration`` (scheduler rungs, checkpoint
    numbering) would claim progress the restored state doesn't have.
    Shared by both executors; runs before the incarnation's first report,
    so the runner never sees the intermediate state.
    """
    if not trial.restore_path:
        return
    if tree is None:
        print(
            f"[executor] WARNING: no checksum-valid checkpoint for "
            f"{trial.trial_id} (wanted {trial.restore_path}); restarting "
            f"from scratch",
            flush=True,
        )
        trial.restore_path = None
        trial.restore_base = 0
        trial.latest_checkpoint = None
        trial.latest_checkpoint_iteration = 0
    elif used_path != trial.restore_path:
        print(
            f"[executor] WARNING: {trial.trial_id} restore fell back "
            f"{trial.restore_path} -> {used_path} (iteration "
            f"{used_iteration})",
            flush=True,
        )
        trial.restore_path = used_path
        trial.restore_base = used_iteration
        trial.latest_checkpoint = used_path
        trial.latest_checkpoint_iteration = used_iteration


class ResultEvent:
    __slots__ = ("trial", "metrics", "decision", "done", "incarnation")

    def __init__(self, trial: Trial, metrics: Dict, incarnation: int = 0):
        self.trial = trial
        self.metrics = metrics
        self.decision = "continue"
        self.done = threading.Event()
        self.incarnation = incarnation


class ThreadTrialExecutor:
    """Runs each trial in a daemon thread pinned to its leased devices."""

    def __init__(self, store, event_queue: "queue.Queue", watchdog=None):
        self.store = store
        self.events = event_queue
        # Optional liveness.DispatchWatchdog (runner-owned): report
        # boundaries and tune.heartbeat() calls beat it; the runner polls
        # expiry.  Threads cannot be preempted, so a stall here is marked,
        # never killed (the process executor owns the kill response).
        self.watchdog = watchdog
        self._threads: Dict[str, threading.Thread] = {}
        # Async checkpoint writes: trials resume training while the D2H
        # transfer + serialization + IO run on the writer thread. Safe
        # in-process because every restore below waits on the path first
        # (ckpt_lib.AsyncCheckpointWriter's contract).
        self._ckpt_writer = ckpt_lib.AsyncCheckpointWriter()

    def start_trial(self, trial: Trial, trainable: Callable, leased_devices: List):
        devices = [d for _, d in leased_devices]
        trial.assigned_devices = leased_devices
        thread = threading.Thread(
            target=self._run,
            args=(trial, trainable, devices, trial.incarnation),
            name=f"trial-{trial.trial_id}",
            daemon=True,
        )
        self._threads[trial.trial_id] = thread
        thread.start()

    def is_alive(self, trial: Trial) -> bool:
        t = self._threads.get(trial.trial_id)
        return t is not None and t.is_alive()

    def join_all(self, timeout: float = 5.0):
        """Best-effort wait (shared deadline): daemon threads can't be
        preempted, so a still-running trial is simply abandoned."""
        deadline = time.monotonic() + timeout
        for t in self._threads.values():
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        # Flush pending checkpoint writes so the experiment directory is
        # complete (resume reads it) before the runner returns.
        self._ckpt_writer.close()

    # -- trial thread body ---------------------------------------------------
    def _run(self, trial: Trial, trainable: Callable, devices: List,
             incarnation: int = 0):
        # Compile-time accounting: jit compiles triggered by this trial run on
        # this thread, so the tracker's per-thread counters are per-trial.
        tracker = get_tracker()
        compile_base = tracker.thread_seconds()
        hits_base = tracker.thread_cache_hits()

        writer_hung = [False]  # one hung write wedges the single writer
        # thread for good — every later submit would queue behind it, so
        # after the first 120s timeout this incarnation stops checkpointing
        # instead of stalling +120s per epoch forever (advisor r3).
        pending_writes = deque()  # this incarnation's in-flight ckpt paths

        def report_fn(metrics: Dict, checkpoint) -> str:
            with obs.span("report", {
                "trial_id": trial.trial_id,
                "iteration": trial.training_iteration + 1,
            }):
                return _report(metrics, checkpoint)

        def _report(metrics: Dict, checkpoint) -> str:
            # Chaos hooks (no-op without an active plan): an injected hang
            # sleeps HERE — before the result reaches the runner — so the
            # report gap the liveness watchdog measures actually opens; an
            # injected crash raises out of session.report inside the
            # trainable and follows the ordinary error path — retry budget,
            # checkpoint restore, device release.
            from distributed_machine_learning_tpu import chaos

            plan = chaos.active_plan()
            if plan is not None:
                plan.maybe_hang_dispatch(
                    trial.trial_id, trial.training_iteration + 1
                )
                plan.maybe_crash_trial(
                    trial.trial_id, trial.training_iteration + 1
                )
            metrics.setdefault(
                "compile_time_s",
                round(tracker.thread_seconds() - compile_base, 4),
            )
            metrics.setdefault(
                "compile_cache_hits", tracker.thread_cache_hits() - hits_base
            )
            # Every report boundary is one training step for the ckpt
            # overlap counters: an async write still in flight when the
            # next step reports is a demonstrably overlapped save.
            ckpt_metrics.note_step()
            obs.event("report", {
                "trial_id": trial.trial_id,
                "iteration": trial.training_iteration + 1,
            })
            if checkpoint is not None and writer_hung[0]:
                checkpoint = None
            if checkpoint is not None:
                count = trial.training_iteration + 1
                path = ckpt_lib.checkpoint_path(
                    self.store.checkpoint_dir(trial), count,
                    getattr(self.store, "checkpoint_format", "msgpack"),
                )
                # Depth-2 write pipeline per trial: before queueing this
                # write, drain down to one in-flight by waiting on the
                # OLDEST pending path — one occasionally-slow write
                # overlaps TWO epochs of training instead of stalling the
                # trial thread (depth 1 stalled whenever write time
                # exceeded epoch time).  FIFO waits keep the synchronous-
                # save error semantics: a write ERROR re-raises here (one
                # epoch later than it occurred; the trial fails and
                # retries), and a HUNG write never deadlocks the trial —
                # bounded wait, then checkpointing is disabled for this
                # incarnation (the single writer thread is wedged for
                # good; teardown abandons the stuck write).
                skip = False
                while len(pending_writes) >= 2:
                    oldest = pending_writes.popleft()
                    with obs.span("report.ckpt_drain"):
                        drained = self._ckpt_writer.wait(oldest, timeout=120.0)
                    if not drained:
                        print(
                            f"[executor] WARNING: checkpoint write for "
                            f"{trial.trial_id} still hung after 120s; "
                            f"disabling checkpointing for the rest of this "
                            f"incarnation (epoch-{count} checkpoint "
                            f"dropped)",
                            flush=True,
                        )
                        writer_hung[0] = True
                        skip = True
                        break
                if not skip:
                    self._ckpt_writer.submit(path, checkpoint)
                    pending_writes.append(path)
                    trial.latest_checkpoint = path
                    trial.latest_checkpoint_iteration = count
            event = ResultEvent(trial, metrics, incarnation)
            self.events.put(("result", event))
            with obs.span("report.decide_wait"):
                event.done.wait()
            return event.decision

        def checkpoint_loader():
            # The restore target may still be in flight on the writer
            # thread (fast PBT exploit, immediate retry) — wait for THAT
            # path to be durable before reading it. Bounded: a hung write
            # degrades to a from-scratch restart, never a deadlocked trial.
            if trial.restore_path and not self._ckpt_writer.wait(
                trial.restore_path, timeout=120.0
            ):
                print(
                    f"[executor] WARNING: restore target for "
                    f"{trial.trial_id} still being written after 120s; "
                    f"restarting without it",
                    flush=True,
                )
                return None
            tree, used, used_it = ckpt_lib.load_checkpoint_with_fallback(
                trial.restore_path, self.store.checkpoint_dir(trial),
            )
            _rewind_after_fallback(trial, tree, used, used_it)
            return tree

        heartbeat_fn = None
        if self.watchdog is not None:
            heartbeat_fn = lambda: self.watchdog.beat(trial.trial_id)  # noqa: E731
        set_session(Session(trial, report_fn, checkpoint_loader, devices,
                            heartbeat_fn=heartbeat_fn))
        try:
            # The obs span parents under the driver's trial.dispatch span
            # (same thread stack from here on: epoch/report spans nest), and
            # tags this trial's host activity in any profiler capture.
            with jax.default_device(devices[0]), obs.span(
                "trial",
                {"trial_id": trial.trial_id, "incarnation": incarnation},
                parent=getattr(trial, "_obs_parent", None),
            ):
                trainable(dict(trial.config))
            self.events.put(("complete", trial, None, incarnation))
        except (StopTrial, PauseTrial):
            self.events.put(("complete", trial, None, incarnation))
        except BaseException:  # noqa: BLE001 - report crash to the runner
            self.events.put(("error", trial, traceback.format_exc(), incarnation))
        finally:
            set_session(None)


_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _host_chip_ordinals(devices: List) -> List[int]:
    """Host-local CHIP ordinals for ``TPU_VISIBLE_CHIPS``.

    Lease bookkeeping indexes into a possibly user-filtered device list, and
    on v2/v3 each chip exposes two cores — neither of which matches what
    ``TPU_VISIBLE_CHIPS`` wants (chip numbers among THIS host's chips).  Map
    each leased device to its chip via physical ``coords`` (cores on one chip
    share coords), numbering chips in this host's device-enumeration order.
    """
    try:
        import jax as _jax

        host_devices = _jax.local_devices()
    except Exception:  # pragma: no cover - backend gone; fall back to ids
        return sorted({getattr(d, "id", 0) for d in devices})
    chip_of: Dict = {}
    for d in host_devices:
        key = tuple(getattr(d, "coords", None) or (d.id,))
        chip_of.setdefault(key, len(chip_of))
    return sorted(
        {chip_of[tuple(getattr(d, "coords", None) or (d.id,))] for d in devices}
    )


class ProcessTrialExecutor:
    """Runs each trial in its OWN OS process, with hard kill support.

    The thread executor cannot preempt a wedged trial (a hung jit compile or
    a stuck epoch loop holds its core until the trainable next reports).
    This executor trades per-trial process startup (~1s CPU / a few s TPU
    init) for real isolation: the runner can :meth:`kill` a trial past its
    time limit, and its device lease is freed immediately — the capability
    the reference got from Ray's actor-per-trial model (SURVEY.md §2b D5).

    Device isolation is by process environment, the TPU analogue of Ray
    setting ``CUDA_VISIBLE_DEVICES`` (`ray-tune-hpo-regression.py:286`):
    ``TPU_VISIBLE_CHIPS``/``TPU_VISIBLE_DEVICES`` for the leased chips on
    real TPU, ``--xla_force_host_platform_device_count`` on the CPU test
    platform.  Trainables and their ``with_parameters`` bindings must be
    picklable.  Checkpoints flow back over the pipe and are persisted by the
    parent, so ``mem://``/``gs://`` checkpoint storage works unchanged.
    """

    supports_kill = True

    def __init__(self, store, event_queue: "queue.Queue", watchdog=None,
                 prewarm: int = 0):
        if jax.devices()[0].platform == "tpu":
            # Seen on a v5e chip: the driver enumerates devices, so it
            # holds the chip, and every child then dies at backend start-up
            # ("Internal error when accessing libtpu multi-process
            # lockfile") — one process at a time may hold a TPU.
            raise RuntimeError(
                "trial_executor='process' cannot run on a TPU from a driver "
                "that has initialized jax: the driver holds the chip(s) and "
                "a child process that needs them fails at backend start-up "
                "(one process at a time may hold a TPU). Use "
                "trial_executor='thread' (one process drives every local "
                "chip), or run_distributed with workers started by a parent "
                "that never imports jax."
            )
        self.store = store
        self.events = event_queue
        # Optional liveness.DispatchWatchdog: result and "beat" frames from
        # the child beat it; the runner's expiry poll calls kill() — the
        # stall response this executor exists to provide.
        self.watchdog = watchdog
        self._procs: Dict[str, subprocess.Popen] = {}
        self._pumps: Dict[str, threading.Thread] = {}
        # Pre-warmed runner pool (compile-once tentpole): children spawned
        # BEFORE their trial is assigned, with DML_PREWARM=1 so they
        # front-load jax import + device enumeration + compile-cache attach
        # and then block on stdin.  start_trial hands a pending init frame
        # to a matching warm child instead of paying a cold Popen + import;
        # the pool replenishes in the background after each take.  Entries
        # are keyed by their exact child environment — a warm child is only
        # usable for a lease that produces the SAME env (device visibility
        # is per-process), so on multi-chip leases the pool simply misses
        # and the cold path runs.
        self._prewarm = max(int(prewarm), 0)
        self._pool_lock = named_lock("tune.executor.prewarm_pool")
        self._pool: List[Tuple[tuple, subprocess.Popen]] = []
        self._prewarmed_keys: set = set()
        self._closing = False
        if self._prewarm:
            try:
                env = self._child_env([jax.devices()[0]])
            except Exception:  # noqa: BLE001 - no backend yet; pool idles
                env = None
            if env is not None:
                for _ in range(self._prewarm):
                    self._add_warm_child(env)

    # -- env -----------------------------------------------------------------
    def _child_env(self, devices: List) -> dict:
        env = dict(os.environ)
        platform = devices[0].platform
        if platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            # The child sees exactly as many virtual devices as it leased.
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+",
                "",
                env.get("XLA_FLAGS", ""),
            ).strip()
            env["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={len(devices)}"
            ).strip()
        else:
            visible = ",".join(str(c) for c in _host_chip_ordinals(devices))
            env["TPU_VISIBLE_CHIPS"] = visible
            env["TPU_VISIBLE_DEVICES"] = visible
        env["PYTHONPATH"] = os.pathsep.join(
            [_REPO_ROOT, env.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
        return env

    # -- pre-warmed pool -----------------------------------------------------
    @staticmethod
    def _env_key(env: dict) -> tuple:
        from distributed_machine_learning_tpu.tune._process_child import (
            PREWARM_ENV,
        )

        return tuple(sorted(
            (k, v) for k, v in env.items() if k != PREWARM_ENV
        ))

    def _spawn(self, env: dict, warm: bool) -> subprocess.Popen:
        from distributed_machine_learning_tpu.tune._process_child import (
            PREWARM_ENV,
        )

        if warm:
            env = dict(env, **{PREWARM_ENV: "1"})
        return subprocess.Popen(
            [sys.executable, "-m",
             "distributed_machine_learning_tpu.tune._process_child"],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=None,  # trainable prints/tracebacks pass through
            env=env,
            cwd=_REPO_ROOT,
        )

    def _add_warm_child(self, env: dict) -> None:
        proc = self._spawn(env, warm=True)
        with self._pool_lock:
            if self._closing:
                proc.terminate()
                return
            self._pool.append((self._env_key(env), proc))

    def _take_warm_child(self, env: dict) -> Optional[subprocess.Popen]:
        """Pop a live warm child whose environment matches ``env`` exactly
        (device visibility is baked into the child process) and replenish
        the slot in the background — by the next dispatch the pool is hot
        for THIS lease shape, even if the initial fill guessed another."""
        want = self._env_key(env)
        with self._pool_lock:
            for i, (key, proc) in enumerate(self._pool):
                if key == want and proc.poll() is None:
                    del self._pool[i]
                    break
            else:
                proc = None
            # read under the pool lock: close() flips it under the same
            # lock, and an unlocked read here could replenish the pool
            # mid-shutdown (dmlint DML014 unguarded-shared-state)
            closing = self._closing
        if self._prewarm and not closing:
            threading.Thread(
                target=self._add_warm_child, args=(dict(env),),
                name="runner-prewarm", daemon=True,
            ).start()
        return proc

    def prewarm_program(self, trainable: Callable, config: Dict,
                        key: str) -> bool:
        """Think-time precompile: ask an idle warm child to trace + compile
        the programs ``config`` needs (it stops at the first report
        boundary), populating the shared persistent/AOT caches before any
        trial with this program key is dispatched.  Fire-and-forget: the
        ack frame is consumed (and skipped) by whichever pump later adopts
        the child.  Returns whether a request was sent."""
        if key in self._prewarmed_keys:
            return False
        with self._pool_lock:
            target = next(
                (proc for _, proc in self._pool if proc.poll() is None), None
            )
        if target is None:
            return False
        try:
            import cloudpickle

            from distributed_machine_learning_tpu.tune import (
                _process_child as pc,
            )

            pc.write_frame(
                target.stdin,
                ("precompile", {
                    "key": key,
                    "trainable": cloudpickle.dumps(trainable),
                    "config": dict(config),
                    "sys_path": list(sys.path),
                }),
            )
        except (OSError, ValueError):
            return False  # child died or stdin closed; pool self-heals
        self._prewarmed_keys.add(key)
        get_compile_counters().add("prewarm_compiles")
        return True

    # -- lifecycle -----------------------------------------------------------
    def start_trial(self, trial: Trial, trainable: Callable, leased_devices: List):
        trial.assigned_devices = leased_devices
        trial._kill_reason = None  # fresh incarnation, fresh diagnosis
        env = self._child_env([d for _, d in leased_devices])
        proc = self._take_warm_child(env) if self._prewarm else None
        if proc is not None:
            get_compile_counters().add("prewarmed_spawns")
        else:
            get_compile_counters().add("cold_spawns")
            proc = self._spawn(env, warm=False)
        self._procs[trial.trial_id] = proc
        # The init frame (cloudpickled trainable + restore checkpoint) is
        # written by the pump thread, not here: a dead child's BrokenPipe or
        # a large payload must cost this trial, not stall/abort the runner's
        # event loop.
        pump = threading.Thread(
            target=self._pump,
            args=(trial, trainable, proc, trial.incarnation),
            name=f"trial-pump-{trial.trial_id}",
            daemon=True,
        )
        self._pumps[trial.trial_id] = pump
        pump.start()

    def is_alive(self, trial: Trial) -> bool:
        t = self._pumps.get(trial.trial_id)
        return t is not None and t.is_alive()

    def kill(self, trial: Trial, reason: str = "killed by runner"):
        """Hard-preempt a trial: SIGTERM, then SIGKILL after a grace period.

        The pump thread observes stream EOF and reports ``reason`` as the
        trial's error, so the runner's normal error path (retry budget,
        device release) applies."""
        trial._kill_reason = reason
        proc = self._procs.get(trial.trial_id)
        if proc is None or proc.poll() is not None:
            return
        obs.event("trial_kill", {
            "trial_id": trial.trial_id, "reason": reason,
        })
        proc.terminate()

        def _escalate():
            try:
                proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                proc.kill()

        threading.Thread(target=_escalate, daemon=True).start()

    def join_all(self, timeout: float = 5.0):
        """Terminate every still-running child, then wait for the pumps
        (shared deadline).  Runner teardown calls this so an interrupted
        sweep never leaves orphan trial processes holding devices."""
        with self._pool_lock:
            self._closing = True
            pool = list(self._pool)
            self._pool.clear()
        for _, proc in pool:
            # Unassigned warm children: close stdin (EOF is their exit
            # signal) and terminate; nothing of value is lost.
            try:
                proc.stdin.close()
            except OSError:
                pass
            if proc.poll() is None:
                proc.terminate()
        for proc in list(self._procs.values()):
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + timeout
        for t in list(self._pumps.values()):
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        for proc in list(self._procs.values()) + [p for _, p in pool]:
            if proc.poll() is None:
                proc.kill()
            try:
                proc.wait(timeout=5.0)  # reap — no zombies, chips freed
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass

    # -- parent-side pump thread --------------------------------------------
    def _pump(self, trial: Trial, trainable: Callable, proc: subprocess.Popen,
              incarnation: int = 0):
        from distributed_machine_learning_tpu.tune import _process_child as pc

        from distributed_machine_learning_tpu import chaos

        try:
            import cloudpickle

            restore = None
            if trial.restore_path:
                # Same corruption fallback as the thread executor — the
                # parent owns storage, so the child never sees a damaged
                # checkpoint, only the newest checksum-valid state.
                restore, used, used_it = (
                    ckpt_lib.load_checkpoint_with_fallback(
                        trial.restore_path,
                        self.store.checkpoint_dir(trial),
                    )
                )
                _rewind_after_fallback(trial, restore, used, used_it)
            pc.write_frame(
                proc.stdin,
                {
                    "trial_id": trial.trial_id,
                    "config": dict(trial.config),
                    # cloudpickle, not pickle: drivers define trainables in
                    # __main__ (closures over datasets via with_parameters),
                    # which reference-pickling cannot rebuild in the child.
                    "trainable": cloudpickle.dumps(trainable),
                    "restore": restore,
                    "sys_path": list(sys.path),
                    # Trace context + dump destination: the child's spans
                    # join THIS trial's trace, its SIGTERM handler dumps
                    # its flight ring into the experiment dir.
                    "obs": obs.trace_context_frame(
                        parent=getattr(trial, "_obs_parent", None)
                    ),
                    "incarnation": incarnation,
                },
            )
            while True:
                msg = pc.read_frame(proc.stdout)
                kind = msg[0]
                if kind in ("warm", "prewarmed", "prewarm_error"):
                    # Pool bookkeeping frames from this child's pre-trial
                    # life (readiness ack, think-time precompile results);
                    # queued in the pipe until this pump adopted it.
                    if kind == "prewarm_error":
                        print(
                            f"[executor] prewarm of {msg[1]} failed:\n"
                            f"{msg[2]}", flush=True,
                        )
                    continue
                if kind == "beat":
                    # Mid-epoch tune.heartbeat() from the child: liveness
                    # only — no runner event, no decision.
                    if self.watchdog is not None:
                        self.watchdog.beat(trial.trial_id)
                    continue
                if kind == "result":
                    plan = chaos.active_plan()
                    if plan is not None:
                        # A hang sleeps the pump BEFORE the result event
                        # lands — the runner-visible silence the watchdog
                        # kills through this executor.  A crash raises
                        # InjectedTrialCrash -> the generic error path
                        # below kills/reaps the child and the runner
                        # retries within max_failures (chaos harness).
                        plan.maybe_hang_dispatch(
                            trial.trial_id, trial.training_iteration + 1
                        )
                        plan.maybe_crash_trial(
                            trial.trial_id, trial.training_iteration + 1
                        )
                    metrics, ckpt_bytes = msg[1], msg[2]
                    ckpt_metrics.note_step()
                    if ckpt_bytes is not None:
                        count = trial.training_iteration + 1
                        path = ckpt_lib.checkpoint_path(
                            self.store.checkpoint_dir(trial), count,
                            getattr(self.store, "checkpoint_format",
                                    "msgpack"),
                        )
                        ckpt_lib.save_checkpoint(path, pickle.loads(ckpt_bytes))
                        trial.latest_checkpoint = path
                        trial.latest_checkpoint_iteration = count
                    event = ResultEvent(trial, metrics, incarnation)
                    self.events.put(("result", event))
                    event.done.wait()
                    pc.write_frame(proc.stdin, ("decision", event.decision))
                elif kind == "complete":
                    self.events.put(("complete", trial, None, incarnation))
                    return
                elif kind == "error":
                    self.events.put(("error", trial, msg[1], incarnation))
                    return
        except (EOFError, OSError) as exc:
            reason = getattr(trial, "_kill_reason", None) or (
                f"trial process died unexpectedly "
                f"(rc={proc.poll()}, {exc!r})"
            )
            self.events.put(("error", trial, reason, incarnation))
        except Exception:  # noqa: BLE001 - e.g. unpicklable trainable
            self.events.put(("error", trial, traceback.format_exc(), incarnation))
        finally:
            try:
                proc.stdin.close()
            except OSError:
                pass
            # Reap the child so it never lingers as a zombie; forget the
            # Popen (a retry incarnation gets fresh entries).
            try:
                proc.wait(timeout=15.0)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5.0)
            # Identity-guarded: a retry incarnation may already have
            # registered ITS proc under this trial_id.
            if self._procs.get(trial.trial_id) is proc:
                self._procs.pop(trial.trial_id, None)

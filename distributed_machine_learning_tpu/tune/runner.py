"""The tune.run() driver loop.

Native, single-process replacement for ``tune.run(...)``
(`ray-tune-hpo-regression.py:469-478`): samples trial configs from the search
algorithm, leases TPU cores from the DeviceManager, streams per-epoch results
through the scheduler, early-stops / requeues / retries, persists everything to
the experiment store, and returns an ExperimentAnalysis with ``best_config``
(`:480`).

Event-driven: trial threads block in ``report`` until this loop answers, so
all scheduler/searcher state is mutated from exactly one thread.
"""

from __future__ import annotations

import queue
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Union

from distributed_machine_learning_tpu.tune.executor import (
    DeviceManager,
    ProcessTrialExecutor,
    ThreadTrialExecutor,
)
from distributed_machine_learning_tpu.tune.experiment import (
    ExperimentAnalysis,
    ExperimentStore,
)
from distributed_machine_learning_tpu.tune._driver import (
    TrialLifecycle,
    scheduler_debug_block,
)
from distributed_machine_learning_tpu.tune.schedulers.base import (
    FIFOScheduler,
    TrialScheduler,
)
from distributed_machine_learning_tpu.tune.search.base import (
    RandomSearch,
    Searcher,
    maybe_warm_start,
)
from distributed_machine_learning_tpu.tune.search_space import SearchSpace
from distributed_machine_learning_tpu.tune.trial import (
    Resources,
    Trial,
    TrialStatus,
)

DEFAULT_STORAGE = "~/dml_tpu_results"


def _validate_resume(storage_path: str, name: Optional[str]) -> None:
    """Shared resume precondition for both drivers: an explicit name whose
    experiment directory actually exists — a typo'd name must not silently
    start (and pay for) a fresh experiment while claiming to resume."""
    import os

    if not name:
        raise ValueError(
            "resume=True needs the explicit experiment `name` to resume"
        )
    root = ExperimentStore.root_for(storage_path, name)
    if not os.path.isdir(root):
        raise FileNotFoundError(
            f"resume=True but no experiment directory at {root}"
        )


def run(
    trainable: Callable,
    param_space: Union[Dict[str, Any], SearchSpace],
    *,
    metric: str,
    mode: str = "min",
    num_samples: int = 10,
    scheduler: Optional[TrialScheduler] = None,
    search_alg: Optional[Searcher] = None,
    resources_per_trial: Optional[Dict[str, int]] = None,
    mesh_shape: Optional[Dict[str, int]] = None,
    input_mode: Optional[str] = None,
    max_concurrent: Optional[int] = None,
    storage_path: str = DEFAULT_STORAGE,
    name: Optional[str] = None,
    seed: int = 0,
    max_failures: int = 0,
    stop=None,
    time_budget_s: Optional[float] = None,
    devices: Optional[List] = None,
    verbose: int = 1,
    callbacks: Optional[List] = None,
    keep_checkpoints_num: int = 0,
    checkpoint_storage: Optional[str] = None,
    checkpoint_format: str = "msgpack",
    compile_cache_dir: Optional[str] = "auto",
    time_limit_per_trial_s: Optional[float] = None,
    trial_executor: str = "thread",
    prewarm_runners: int = 0,
    resume: Union[bool, str] = False,
    points_to_evaluate: Optional[List[Dict[str, Any]]] = None,
    progress_deadline_s: Optional[float] = None,
    progress_grace_s: Optional[float] = None,
    trace: bool = False,
) -> ExperimentAnalysis:
    """Run an HPO experiment; see module docstring.

    ``points_to_evaluate``: configs (possibly partial — missing keys are
    sampled) run as the first trials before the searcher proposes its own;
    model-based searchers observe their results (Ray's knob of the same
    name).

    ``mesh_shape``: sweep-wide 2-D (or N-D) device mesh per trial, e.g.
    ``{"dp": 2, "tp": 4}`` — stamped into every sampled config (a config
    that carries its own ``mesh_shape`` wins) and, when
    ``resources_per_trial`` is omitted, the per-trial device lease
    defaults to the mesh's total size, so
    ``tune.run(trainable, space, mesh_shape={"dp": 2, "tp": 4})`` leases
    8 devices per trial and the sharded trainable builds the mesh from
    its model family's partition rules (``models/partition_rules.py``).
    ``input_mode``: sweep-wide data staging mode stamped into every sampled
    config (a config carrying its own ``input_mode`` wins) — ``"resident"``
    (HBM-resident epochs; raises when the staged dataset exceeds the
    device budget), ``"streaming"`` (the out-of-core prefetch ring,
    ``data/pipeline.py``), or ``"auto"`` (the default: streaming engages
    when the dataset exceeds ``streaming_engage_fraction`` of the budget).
    Streaming runs publish the ``host_input`` counter block
    (prefetch hits, producer/consumer waits, overlap efficiency) into
    ``experiment_state.json`` and TensorBoard ``host_input/*``.
    ``stop``: dict of result-key -> threshold (a trial stops once any key's
    reported value reaches it, e.g. ``{"training_iteration": 20}``), a
    callable ``(trial_id, result) -> bool``, or a ``tune.Stopper``
    (``TrialPlateauStopper``, ``MaximumIterationStopper`` —
    tune/stoppers.py).
    ``max_failures``: per-trial retry budget; retries restore from the trial's
    latest checkpoint when one exists (preemption tolerance, SURVEY.md §5).
    ``keep_checkpoints_num``: retention — keep only the newest k checkpoints
    per trial (0 = keep all); checkpoints referenced by a pending PBT exploit
    or retry are never pruned.
    ``checkpoint_storage``: alternate root for checkpoints (``gs://...`` for
    shared pod storage, ``mem://...`` in tests); metrics stay local.
    ``checkpoint_format``: ``"msgpack"`` (legacy single-blob flax msgpack,
    the default and what existing experiment directories hold) or
    ``"sharded"`` (the ``ckpt/`` chunked format: per-shard files + JSON
    index + atomic COMMIT marker — async-friendly and restorable onto a
    different mesh/device count).  Restores handle both regardless, so an
    experiment can be resumed across the switch; save/restore wall, bytes,
    and async-overlap counters land in
    ``experiment_state.json["checkpoint"]`` and TensorBoard either way.
    ``compile_cache_dir``: persistent XLA compile-cache directory ("auto" =
    the fixed in-checkout ``.dml_cache/xla``; None disables;
    ``$JAX_COMPILATION_CACHE_DIR``, when set, wins over any directory given
    here).  The framework owns compile-time amortization (SURVEY.md §7):
    identical-architecture trials skip XLA backend compilation, and every
    result record carries ``compile_time_s`` / ``compile_cache_hits``.
    ``time_limit_per_trial_s``: per-trial wall-clock budget.  Enforced softly
    at every report boundary (both executors), and enforced HARD — SIGTERM,
    then SIGKILL — for trials that stop reporting (a wedged jit, a stuck
    epoch loop) when ``trial_executor="process"``.  A killed trial follows
    the normal error path: retried within ``max_failures`` (restoring its
    latest checkpoint) or marked ERROR, and its devices are re-leased either
    way.
    ``trial_executor``: "thread" (default; lowest overhead, no preemption) or
    "process" (one OS process per trial with per-process device visibility;
    requires picklable trainables; refused on a TPU, where this driver
    holds the chips and a child that needs one cannot open it).
    ``prewarm_runners``: with ``trial_executor="process"``, keep this many
    PRE-WARMED runner children pooled: spawned before any trial is
    assigned, they front-load jax import + device enumeration + compile-
    cache attach, so dispatch-to-first-step latency collapses to frame
    parsing.  During scheduler think-time the runner also asks an idle
    warm child to PRE-COMPILE the next pending trial's program (it stops
    at the first report boundary), so a cold program key is hot in the
    shared persistent/AOT caches before its trial ever launches.
    Counters (``prewarmed_spawns``/``cold_spawns``/``prewarm_compiles``)
    land in ``experiment_state.json["compile"]``.  0 disables (default).
    ``progress_deadline_s``: fail-SLOW detection (liveness.py).  Where
    ``time_limit_per_trial_s`` bounds total runtime, this bounds SILENCE:
    a trial that produces no progress signal (``tune.report`` or
    ``tune.heartbeat``) for this long is marked STALLED — and, under the
    process executor, killed and restarted from its newest checkpoint
    within ``max_failures`` (the thread executor cannot preempt; the stall
    is marked, counted, and cleared if the trial recovers).  Counters land
    in ``experiment_state.json["liveness"]`` and TensorBoard.  Size it
    comfortably above the slowest legitimate report gap (or call
    ``tune.heartbeat()`` inside long epochs).
    ``progress_grace_s``: extra allowance before each incarnation's FIRST
    progress signal (process spawn, jax import, cold compile; default
    ``max(3 * deadline, 30)``) so startup latency is never misread as a
    stall.
    ``resume``: continue an interrupted experiment (requires an explicit
    ``name`` pointing at its directory): finished trials are kept and their
    metric streams replayed into the scheduler/searcher, interrupted trials
    re-run from their newest checkpoint, and sampling continues to
    ``num_samples`` — driver-crash / preemption recovery for the whole
    experiment, not just single trials.
    ``trace``: structured tracing (``obs/``, docs/observability.md; also
    enabled by ``DML_OBS_TRACE=1``): every process in the run — driver,
    process-executor children — streams spans (trial lifecycle, epochs,
    compiles, checkpoint save/restore, prefetch waits) to per-process
    files under ``<experiment>/trace/``, merged into a Chrome-trace/
    Perfetto ``trace.json`` at experiment end (``dml-tpu trace`` to
    export/summarize).  Trace ids are consistent across the process
    boundary.  Off (the default), the instrumentation costs one
    None-check per span.  Either way the run points the always-on flight
    recorder at the experiment root: a stall, kill, or SIGTERM dumps the
    last ~2048 events (``flightrec_*.json``) with per-thread open-span
    stacks — the hang site, not just a counter.  Independent of ``trace``,
    every span also lands in any running ``jax.profiler`` capture
    (``ProfilerCallback``) as ``dml:<name>``, on the device trace's clock.
    """
    if mode not in ("min", "max"):
        raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
    from distributed_machine_learning_tpu import obs as obs_lib
    from distributed_machine_learning_tpu.tune import journal as journal_lib

    # Store, journal, executor, lifecycle: ended where the experiment span
    # opens; a set-up that raises drops it.
    setup_span = obs_lib.span("run.setup")

    # resume="auto": resume IFF a prior head left an uncommitted decision
    # journal behind (crash mid-sweep); a committed journal or no journal
    # means the experiment either finished cleanly or never started, and the
    # run proceeds fresh.  Unlike resume=True this never raises on a missing
    # directory — "auto" is safe to pass unconditionally in supervisor loops.
    journal_resume = False
    if resume == "auto":
        if not name:
            raise ValueError(
                'resume="auto" needs the explicit experiment `name`'
            )
        journal_resume = journal_lib.is_uncommitted(
            ExperimentStore.root_for(storage_path, name)
        )
        resume = journal_resume
    if resume:
        _validate_resume(storage_path, name)
    if compile_cache_dir is not None:
        from distributed_machine_learning_tpu.utils.compile_cache import (
            enable_persistent_cache,
        )

        enable_persistent_cache(
            None if compile_cache_dir == "auto" else compile_cache_dir
        )
    space = (
        param_space
        if isinstance(param_space, SearchSpace)
        else SearchSpace(param_space)
    )
    from distributed_machine_learning_tpu.tune.stoppers import resolve_stop

    stop = resolve_stop(stop)  # validate dict/callable/Stopper up front
    searcher = maybe_warm_start(search_alg or RandomSearch(), points_to_evaluate)
    searcher.set_search_space(space, seed)
    sched = scheduler or FIFOScheduler()
    sched.set_experiment(metric, mode)
    if mesh_shape is not None and resources_per_trial is None:
        # The mesh IS the resource request: lease exactly as many devices
        # as the axes multiply out to.
        import math

        resources_per_trial = {
            "devices": math.prod(int(v) for v in mesh_shape.values())
        }
    resources = Resources.parse(resources_per_trial)

    name = name or f"exp_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:6]}"
    store = ExperimentStore(
        storage_path, name, checkpoint_storage,
        checkpoint_format=checkpoint_format,
    )
    store.set_context(metric, mode)
    from distributed_machine_learning_tpu.ckpt import get_metrics
    from distributed_machine_learning_tpu import compilecache

    ckpt_metrics_base = get_metrics().snapshot()
    # Scope the process-wide compile registries to THIS run (same
    # discipline as the checkpoint counters).
    compile_tracker_base = compilecache.get_tracker().snapshot()
    compile_counters_base = compilecache.get_counters().snapshot()
    from distributed_machine_learning_tpu.data import pipeline as hostpipe

    if input_mode is not None and input_mode not in hostpipe.INPUT_MODES:
        raise ValueError(
            f"input_mode must be one of {hostpipe.INPUT_MODES}, "
            f"got {input_mode!r}"
        )
    host_input_base = hostpipe.get_host_input_counters().snapshot()
    # Observability plane (obs/): flight-recorder dumps land in the
    # experiment root for THIS run; tracing (opt-in) streams spans to
    # <root>/trace/ per process, merged at teardown.
    import os as _os

    trace = trace or _os.environ.get("DML_OBS_TRACE") == "1"
    trace_dir = _os.path.join(store.root, "trace") if trace else None
    prev_dump_dir = obs_lib.dump_dir()
    # Journal-based resume adopts the dead head's trace identity BEFORE the
    # tracer is configured, so one trace id spans both head incarnations —
    # the resumed sweep's spans merge into the same trace.json.
    replay = journal_lib.parse_journal(store.root) if journal_resume else None
    prior_frame = (replay.trace_frame if replay is not None else None) or {}
    obs_lib.configure(trace_dir=trace_dir, label="driver",
                      dump_dir=store.root,
                      trace_id=prior_frame.get("trace_id"),
                      parent_span_id=prior_frame.get("parent_span_id"))
    # Every scheduling decision is journaled (write-ahead) before it takes
    # effect; `journal.commit()` at clean teardown is what "auto" checks for.
    journal = journal_lib.ExperimentJournal(store.root)
    head_incarnation = journal.open(obs_frame=obs_lib.trace_context_frame())
    obs_counters_base = obs_lib.get_registry().counters_snapshot()
    device_mgr = DeviceManager(devices)
    events: "queue.Queue" = queue.Queue()
    watchdog = None
    if progress_deadline_s is not None:
        from distributed_machine_learning_tpu.liveness import DispatchWatchdog

        # Polled from the event loop below (which ticks every <=0.5s); no
        # monitor thread needed.
        watchdog = DispatchWatchdog(
            progress_deadline_s, first_beat_grace_s=progress_grace_s
        )
    if trial_executor == "thread":
        executor = ThreadTrialExecutor(store, events, watchdog=watchdog)
    elif trial_executor == "process":
        executor = ProcessTrialExecutor(store, events, watchdog=watchdog,
                                        prewarm=prewarm_runners)
    else:
        raise ValueError(
            f"trial_executor must be 'thread' or 'process', got {trial_executor!r}"
        )
    from distributed_machine_learning_tpu.tune.callbacks import (
        with_default_reporter,
    )

    callbacks = with_default_reporter(callbacks, verbose)

    max_concurrent = max_concurrent or device_mgr.num_devices
    running: Dict[str, List] = {}  # trial_id -> leased devices
    last_status_print = 0.0
    last_sched_persist = 0.0

    def log(msg: str):
        if verbose:
            print(f"[tune] {msg}", flush=True)

    lifecycle = TrialLifecycle(
        searcher=searcher,
        scheduler=sched,
        store=store,
        metric=metric,
        mode=mode,
        num_samples=num_samples,
        max_failures=max_failures,
        stop_rules=stop,
        time_budget_s=time_budget_s,
        keep_checkpoints_num=keep_checkpoints_num,
        time_limit_per_trial_s=time_limit_per_trial_s,
        log=log,
        config_overlay={
            **({"mesh_shape": dict(mesh_shape)} if mesh_shape else {}),
            **({"input_mode": input_mode} if input_mode else {}),
        } or None,
        journal=journal,
    )
    trials = lifecycle.trials
    pending = lifecycle.pending
    start_time = lifecycle.start_time

    liveness_counters = {"stall_kills": 0, "stall_requeues": 0}
    if watchdog is not None:
        # The liveness family in the unified registry: watchdog counters +
        # the runner's kill/requeue responses, live (the published
        # experiment_state.json block keeps its existing shape below).
        obs_lib.get_registry().register_family(
            "liveness",
            lambda: {**watchdog.snapshot(), **liveness_counters},
        )

    if journal_resume and replay is not None:
        counts = lifecycle.restore_from_journal(replay, resources=resources)
        log(
            f"resumed {name} from journal (head incarnation "
            f"{head_incarnation}): {counts['finished']} finished trials "
            f"kept, {counts['requeued']} interrupted trials requeued, "
            f"{counts['suppress_windows']} replay suppression windows"
        )
    elif resume:
        counts = lifecycle.restore_experiment(resources=resources)
        log(
            f"resumed {name}: {counts['finished']} finished trials kept, "
            f"{counts['requeued']} interrupted trials requeued"
        )

    def safe_cb(hook: str, *args):
        from distributed_machine_learning_tpu.tune.callbacks import (
            dispatch_safely,
        )

        dispatch_safely(callbacks, hook, *args, log=log)

    trial_spans: Dict[str, Any] = {}  # trial_id -> open dispatch span

    def launch_ready():
        while pending and len(running) < max_concurrent:
            leased = device_mgr.acquire(pending[0].resources.devices)
            if leased is None:
                return
            trial = pending.pop(0)
            lifecycle.mark_running(trial)
            running[trial.trial_id] = leased
            if watchdog is not None:
                watchdog.track(trial.trial_id)
            # Driver-side dispatch span (detached: it closes on a later
            # event-loop iteration); the executor parents the in-trial
            # span under it — across the process boundary too.
            span = obs_lib.detached_span(
                "trial.dispatch",
                {"trial_id": trial.trial_id,
                 "incarnation": trial.incarnation},
                parent=obs_lib.current_context(),
            )
            trial_spans[trial.trial_id] = span
            trial._obs_parent = span.context
            obs_lib.event("trial_dispatch", {"trial_id": trial.trial_id})
            safe_cb("on_trial_start", trial)
            executor.start_trial(trial, trainable, leased)

    def release_devices(trial: Trial):
        leased = running.pop(trial.trial_id, None)
        if leased:
            device_mgr.release(leased)
        if watchdog is not None:
            watchdog.untrack(trial.trial_id)
        span = trial_spans.pop(trial.trial_id, None)
        if span is not None:
            span.end()

    # -------- main event loop ------------------------------------------------
    last_enforce = [0.0]
    _STALL_PREFIX = "stalled: no progress signal"

    def enforce_liveness():
        """Turn watchdog expiries into actions: kill+restart under the
        process executor (preemption-safe — the error path restores the
        newest checksum-valid checkpoint within max_failures), mark
        STALLED under the thread executor (threads can't be preempted;
        a later beat flips the trial back to RUNNING)."""
        if watchdog is None:
            return
        # Reconcile recoveries first: a beat may have arrived straight from
        # the trial thread (tune.heartbeat()) since the stall was flagged.
        for tid in list(running):
            trial = lifecycle.by_id[tid]
            if (
                trial.status == TrialStatus.STALLED
                and not watchdog.is_stalled(tid)
            ):
                trial.status = TrialStatus.RUNNING
                trial.stall_recoveries += 1
                log(f"{tid} recovered after stall (progress resumed)")
        for event in watchdog.expired():
            trial = lifecycle.by_id.get(event.key)
            if trial is None or trial.trial_id not in running:
                watchdog.untrack(event.key)
                continue
            trial.stall_count += 1
            # Forensics BEFORE the response: the dump carries the last
            # ~2048 process events plus every thread's open-span stack —
            # under the thread executor that includes the stalled trial
            # thread's innermost span, i.e. the hang site.
            obs_lib.dump_flight_recorder(
                f"stall_{trial.trial_id}",
                extra={"trial_id": trial.trial_id,
                       "age_s": round(event.age_s, 2),
                       "deadline_s": event.deadline_s},
            )
            if getattr(executor, "supports_kill", False):
                why = (
                    f"{_STALL_PREFIX} in {event.age_s:.1f}s "
                    f"(deadline {event.deadline_s:.1f}s)"
                )
                log(f"{trial.trial_id} {why}; killing incarnation "
                    f"{trial.incarnation}")
                liveness_counters["stall_kills"] += 1
                executor.kill(trial, why)
            else:
                trial.status = TrialStatus.STALLED
                log(
                    f"{trial.trial_id} STALLED: no progress signal in "
                    f"{event.age_s:.1f}s (deadline {event.deadline_s:.1f}s; "
                    f"thread executor cannot preempt — the mark clears if "
                    f"it beats again; use trial_executor='process' for "
                    f"kill/restart)"
                )

    def enforce_time_limits():
        """Hard preemption: a trial past its time limit that has gone quiet
        (no report) is killed outright when the executor can (process
        executor); the thread executor can only flag it for stop at its next
        report.  Runs on EVERY loop iteration (rate-limited), not just idle
        ones — a busy event stream must not starve enforcement."""
        if time_limit_per_trial_s is None:
            return
        now = time.time()
        if now - last_enforce[0] < 1.0:
            return
        last_enforce[0] = now
        grace = max(2.0, 0.25 * time_limit_per_trial_s)
        for tid in list(running):
            trial = lifecycle.by_id[tid]
            overdue = trial.incarnation_runtime_s() - time_limit_per_trial_s
            if overdue <= grace or not executor.is_alive(trial):
                continue
            if getattr(executor, "supports_kill", False):
                log(
                    f"{trial.trial_id} exceeded time limit "
                    f"({trial.incarnation_runtime_s():.0f}s > "
                    f"{time_limit_per_trial_s:.0f}s); killing"
                )
                executor.kill(
                    trial,
                    f"time limit exceeded ({time_limit_per_trial_s:.0f}s)",
                )
            else:
                trial.stop_requested = True

    def event_loop():
        nonlocal last_status_print, last_sched_persist
        while True:
            while not lifecycle.exhausted() and (
                len(pending) + len(running) < max_concurrent + 2
            ):
                if lifecycle.create_trial(resources=resources) is None:
                    break
            launch_ready()

            if not running and not pending:
                if lifecycle.exhausted():
                    break
                if len(trials) == 0 and lifecycle.next_index == 0:
                    break  # nothing to do at all
                continue

            enforce_time_limits()
            enforce_liveness()
            try:
                event = events.get(timeout=0.5)
            except queue.Empty:
                # Scheduler think-time: ask an idle pre-warmed runner to
                # compile the next pending trial's program so its launch
                # finds every cache hot (no-op without a warm pool; deduped
                # per program key inside the executor).
                if pending and hasattr(executor, "prewarm_program"):
                    cand = pending[0]
                    executor.prewarm_program(
                        trainable, cand.config,
                        compilecache.program_key(cand.config),
                    )
                if verbose and time.time() - last_status_print > 15:
                    last_status_print = time.time()
                    log(
                        f"{sum(t.status == TrialStatus.TERMINATED for t in trials)}"
                        f"/{num_samples} done, {len(running)} running, "
                        f"{device_mgr.num_free}/{device_mgr.num_devices} cores free"
                    )
                # Reap trials whose executor died without a terminal event
                # (shouldn't happen: both executors post one on every path).
                # Routed through fail_trial so the retry budget and error
                # reporting behave exactly like an ordinary trial error.
                for tid in list(running):
                    trial = lifecycle.by_id[tid]
                    if not executor.is_alive(trial):
                        why = "trial executor died without reporting"
                        safe_cb("on_trial_error", trial, why)
                        release_devices(trial)
                        lifecycle.fail_trial(trial, why)
                safe_cb("on_heartbeat")
                continue

            kind = event[0]
            # Stale-event guard: a dead incarnation's late events (kill/EOF
            # races, reaped trials) must not be applied — especially not to
            # a relaunched retry of the same trial.  Anything whose
            # incarnation tag doesn't match the trial's current incarnation,
            # or whose trial is no longer running, is dropped.
            if kind == "result":
                ev_trial, ev_inc = event[1].trial, event[1].incarnation
            else:
                ev_trial = event[1]
                ev_inc = event[3] if len(event) > 3 else ev_trial.incarnation
            if (
                ev_trial.trial_id not in running
                or ev_inc != ev_trial.incarnation
            ):
                if kind == "result":
                    event[1].decision = "stop"
                    event[1].done.set()
                continue

            if kind == "result":
                result_event = event[1]
                trial = result_event.trial
                if watchdog is not None:
                    # A report IS progress: beat before deciding, and a
                    # STALLED-but-reporting trial is a recovery, not a kill.
                    watchdog.beat(trial.trial_id)
                    if trial.status == TrialStatus.STALLED:
                        trial.status = TrialStatus.RUNNING
                        trial.stall_recoveries += 1
                        log(f"{trial.trial_id} recovered after stall "
                            f"(report resumed)")
                with obs_lib.span("runner.process_result", {
                    "trial_id": trial.trial_id,
                    "iteration": trial.training_iteration + 1,
                }):
                    result_event.decision = lifecycle.process_result(
                        trial, result_event.metrics
                    )
                # Unblock the trial thread BEFORE observers run: a slow or
                # buggy callback must not stall (or hang) training.
                result_event.done.set()
                with obs_lib.span("runner.callbacks"):
                    safe_cb("on_trial_result", trial, trial.last_result)
                # Forensics (satellite of the durable-control-plane work):
                # persist the scheduler/searcher debug snapshot at report
                # boundaries, throttled so a chatty sweep doesn't rewrite
                # experiment_state.json on every epoch.
                if time.time() - last_sched_persist > 2.0:
                    last_sched_persist = time.time()
                    with obs_lib.span("runner.write_state"):
                        store.write_state(trials, extra={
                            "scheduler": scheduler_debug_block(
                                searcher, sched
                            ),
                        })

            elif kind == "complete":
                trial = event[1]
                release_devices(trial)
                if not lifecycle.complete_trial(trial):
                    safe_cb("on_trial_complete", trial)
                store.write_state(trials, extra={
                    "scheduler": scheduler_debug_block(searcher, sched),
                })

            elif kind == "error":
                trial, tb = event[1], event[2]
                trial.error = tb
                # Every failure is observable, including ones that will be
                # retried (preemptions are exactly what observers watch for).
                safe_cb("on_trial_error", trial, tb)
                release_devices(trial)
                retried = lifecycle.fail_trial(trial, tb)
                if retried and tb and tb.startswith(_STALL_PREFIX):
                    liveness_counters["stall_requeues"] += 1
                if not retried and verbose:
                    log(f"{trial.trial_id} errored:\n{tb}")
                store.write_state(trials, extra={
                    "scheduler": scheduler_debug_block(searcher, sched),
                })

    # Teardown always runs (Ctrl-C, store errors, a callback's setup raising):
    # callbacks must see experiment end so e.g. ProfilerCallback stops the
    # process-global trace and JsonlCallback closes its file.
    clean_end = False
    setup_span.end()
    try:
        # The experiment root span: every driver-side span (trial
        # dispatches) and, via frame context, every child/worker span
        # shares its trace id.
        with obs_lib.span("experiment", {"name": name}):
            for cb in callbacks:
                cb.setup(store.root, metric, mode)
            event_loop()
        # Reaching here means the sweep drained normally — only then is the
        # journal committed below; an exception (Ctrl-C, store failure)
        # leaves it uncommitted so resume="auto" picks the run back up.
        clean_end = True
    finally:
        # Clock first (teardown time is not experiment time), then tear the
        # executor down: an interrupted sweep must not leave orphan trial
        # processes holding devices (process executor terminates children;
        # thread executor best-effort joins).
        wall = time.time() - start_time
        # Hand-ended after the last callback: the join, the writer's wait
        # for its last write, the final state write, the trace merge.
        teardown_span = obs_lib.span("run.teardown")
        try:
            executor.join_all(timeout=5.0)
        except Exception as exc:  # noqa: BLE001
            log(f"executor teardown failed: {exc!r}")
        # Final retention pass: join_all flushed the async writer, so
        # writes that landed AFTER each trial's last in-run prune now
        # converge to exactly keep_checkpoints_num on disk.
        lifecycle.final_prune()
        utilization = device_mgr.utilization(wall)
        from distributed_machine_learning_tpu import chaos
        from distributed_machine_learning_tpu.utils import compile_cache as cc

        extra = {
            "wall_clock_s": wall,
            "device_utilization": utilization,
            "compile_time_total_s": round(cc.get_tracker().total_seconds(), 3),
            "compile_cache_hits": cc.get_tracker().total_cache_hits(),
            "compile_cache_entries": cc.cache_entry_count(),
            # The compile counter family for THIS run (tracker event counts
            # + artifact-layer counters) — the block the compile-once
            # acceptance checks read.
            "compile": compilecache.state_block(
                compile_tracker_base, compile_counters_base
            ),
        }
        if watchdog is not None:
            # Fail-slow observability next to the fail-fast counters: how
            # many silences were detected, killed, requeued, or recovered.
            extra["liveness"] = {**watchdog.snapshot(), **liveness_counters}
        # Checkpoint I/O accounting for THIS run (the registry is
        # process-wide): save/restore wall and bytes, fallbacks taken, and
        # the async-overlap counters that prove training ran while writes
        # were in flight.
        ckpt_counters = get_metrics().delta_since(ckpt_metrics_base)
        if any(ckpt_counters.values()):
            extra["checkpoint"] = ckpt_counters
        # Host-input accounting for THIS run (out-of-core streaming +
        # dataset cache): prefetch hits, producer/consumer waits, and the
        # derived overlap efficiency — present only when something
        # streamed or the dataset cache was touched.
        hi_block = hostpipe.host_input_block(host_input_base)
        if hi_block is not None:
            extra["host_input"] = hi_block
        plan = chaos.active_plan()
        if plan is not None:
            # A chaos run's state snapshot records what was injected, so
            # "it survived N faults" is a property of the artifact, not of
            # test logs.
            extra["injected_faults"] = plan.snapshot()
        from distributed_machine_learning_tpu.tune.schedulers.pbt import (
            pbt_state_block,
        )

        pbt_block = pbt_state_block(sched)
        if pbt_block is not None:
            # The pbt counter family (exploit/explore accounting) — the
            # respawn driver's slice of what run_vectorized reports richer
            # (generations/host_dispatches only exist in-device).
            extra["pbt"] = pbt_block
        # Observability-plane accounting + trace merge: close any spans
        # still open (teardown), merge the per-process span files into
        # one Chrome-trace JSON, and publish the obs counter delta.
        for span in trial_spans.values():
            span.end()
        trial_spans.clear()
        merged_trace = None
        if trace_dir is not None:
            obs_lib.flush()
            merged_trace = obs_lib.merge_trace_dir(trace_dir)
            obs_lib.shutdown()
        # Control-plane forensics: final scheduler/searcher snapshot plus
        # the journal counters the crash-recovery runbook keys off
        # (docs/operations.md — head_incarnations / journal_replays /
        # duplicate_reports_suppressed).
        extra["scheduler"] = scheduler_debug_block(searcher, sched)
        extra["journal"] = {
            "head_incarnation": head_incarnation,
            "decisions": journal.n,
            "journal_replays": (
                (replay.replays if replay is not None else 0)
                + (1 if journal_resume else 0)
            ),
            "duplicate_reports_suppressed":
                lifecycle.duplicate_reports_suppressed,
            "committed": clean_end,
        }
        obs_delta = obs_lib.get_registry().delta_since(obs_counters_base)
        obs_block = {k: v for k, v in obs_delta.items() if v}
        if merged_trace is not None:
            obs_block["trace"] = merged_trace
        if obs_block:
            extra["obs"] = obs_block
        if watchdog is not None:
            obs_lib.get_registry().unregister_family("liveness")
        obs_lib.set_dump_dir(prev_dump_dir)
        try:
            store.write_state(trials, extra=extra)
            store.close()
        except Exception as exc:  # noqa: BLE001 - callbacks still tear down
            log(f"experiment store teardown failed: {exc!r}")
        # Commit AFTER the final state write: once the commit record lands,
        # resume="auto" treats the experiment as finished, so everything it
        # would need must already be durable.
        try:
            if clean_end:
                journal.commit()
            journal.close()
        except Exception as exc:  # noqa: BLE001
            log(f"journal teardown failed: {exc!r}")
        counter_scalars = {
            **{f"liveness/{k}": v
               for k, v in (extra.get("liveness") or {}).items()},
            **{f"faults/{k}": v
               for k, v in (extra.get("injected_faults") or {}).items()},
            **{f"checkpoint/{k}": v
               for k, v in (extra.get("checkpoint") or {}).items()},
            **{f"compile/{k}": v
               for k, v in (extra.get("compile") or {}).items()},
            **{f"host_input/{k}": v
               for k, v in (extra.get("host_input") or {}).items()},
            **{f"pbt/{k}": v
               for k, v in (extra.get("pbt") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"obs/{k}": v
               for k, v in (extra.get("obs") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
            **{f"journal/{k}": v
               for k, v in (extra.get("journal") or {}).items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)},
        }
        if counter_scalars:
            safe_cb("on_experiment_counters", counter_scalars)
        safe_cb("on_experiment_end", trials, wall)
        teardown_span.end()
    analysis = ExperimentAnalysis(
        trials, metric=metric, mode=mode, root=store.root, wall_clock_s=wall,
        device_utilization=utilization,
    )
    n_done = analysis.num_terminated()
    log(
        f"experiment {name}: {n_done}/{len(trials)} trials terminated in "
        f"{wall:.1f}s ({analysis.trials_per_hour():.1f} trials/hour, "
        f"{100 * utilization:.0f}% device utilization)"
    )
    return analysis

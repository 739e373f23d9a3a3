"""Shared trial-lifecycle core for the single-host and cluster drivers.

``tune.run`` (runner.py, thread executor on local devices) and
``cluster.run_distributed`` (cluster.py, remote host supervisors) differ only
in *where* trials execute; the lifecycle — sampling configs from the
searcher, stamping and persisting per-epoch results, routing them through the
scheduler, REQUEUE bookkeeping (PBT), retry-with-restore on failure — is one
state machine. This module owns it, so scheduler-protocol changes land in
exactly one place. (The reference delegated all of this to Ray Tune's trial
runner; SURVEY.md §1 L4.)
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

from distributed_machine_learning_tpu import obs
from distributed_machine_learning_tpu.tune.schedulers.base import (
    CONTINUE,
    REQUEUE,
    STOP,
)
from distributed_machine_learning_tpu.tune.stoppers import stop_hit
from distributed_machine_learning_tpu.tune.trial import Trial, TrialStatus


def _summarize(value):
    """Collections collapse to their sizes — forensic shape, not payload
    (a BayesOpt X matrix in experiment_state.json would dwarf the trials)."""
    if isinstance(value, dict):
        return {str(k): _summarize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return len(value)
    return value


def scheduler_debug_block(searcher, scheduler) -> Dict[str, Any]:
    """The ``experiment_state.json["scheduler"]`` forensics block both
    drivers persist at report boundaries (throttled) and at completion
    boundaries: who is deciding, and the summarized shape of their state —
    the first thing a postmortem of a bad stop/exploit wants."""
    block: Dict[str, Any] = {
        "scheduler_type": type(scheduler).__name__,
        "searcher_type": type(searcher).__name__,
    }
    debug = getattr(scheduler, "debug_state", None)
    if callable(debug):
        try:
            block["scheduler_state"] = debug()
        except Exception:  # noqa: BLE001 - forensics never kill a run
            pass
    try:
        block["searcher_state"] = _summarize(searcher.save_state())
    except Exception:  # noqa: BLE001
        pass
    return block


class TrialLifecycle:
    """Single-threaded trial state machine shared by both drivers.

    The executor layer (threads or remote workers) calls in with events;
    this class mutates trial/searcher/scheduler/store state and answers
    with decisions. It never blocks and never touches sockets or devices.
    """

    def __init__(
        self,
        *,
        searcher,
        scheduler,
        store,
        metric: str,
        mode: str,
        num_samples: int,
        max_failures: int = 0,
        stop_rules: Optional[Dict[str, float]] = None,
        time_budget_s: Optional[float] = None,
        keep_checkpoints_num: int = 0,
        time_limit_per_trial_s: Optional[float] = None,
        log: Callable[[str], None] = lambda msg: None,
        config_overlay: Optional[Dict[str, Any]] = None,
        journal=None,
    ):
        self.searcher = searcher
        self.scheduler = scheduler
        self.store = store
        # Write-ahead log (tune/journal.ExperimentJournal, or None): every
        # scheduling decision is journaled with a post-decision
        # searcher/scheduler snapshot BEFORE its externally visible effect,
        # so a killed head resumes to bit-identical decision state.
        self.journal = journal
        self.metric = metric
        self.mode = mode
        self.num_samples = num_samples
        self.max_failures = max_failures
        self.stop_rules = stop_rules or {}
        self.time_budget_s = time_budget_s
        self.keep_checkpoints_num = keep_checkpoints_num
        self.time_limit_per_trial_s = time_limit_per_trial_s
        self.log = log
        # Driver-level config defaults under every sampled config (e.g.
        # tune.run(mesh_shape=...) stamping the sweep-wide mesh shape);
        # a key the search space samples always wins over the overlay.
        self.config_overlay = dict(config_overlay or {})

        self.trials: List[Trial] = []
        self.by_id: Dict[str, Trial] = {}
        self.pending: List[Trial] = []
        self.next_index = 0
        self.searcher_exhausted = False
        self.start_time = time.time()
        # Exactly-once epoch accounting after a journal-based resume:
        # trial_id -> journaled report watermark.  A requeued trial
        # restored from a checkpoint BELOW its watermark re-reports the
        # gap; those re-reports are suppressed (counted, never re-persisted
        # or re-observed) until the watermark is reached.
        self._suppress: Dict[str, int] = {}
        self.duplicate_reports_suppressed = 0

    # -- journal -----------------------------------------------------------

    def _snapshot(self) -> Dict[str, Any]:
        """The decision-state snapshot a journal record carries: restore it
        and the searcher/scheduler make bit-identical decisions from here."""
        return {
            "searcher": self.searcher.save_state(),
            "scheduler": self.scheduler.save_state(),
            "next_index": self.next_index,
        }

    # -- creation ----------------------------------------------------------

    def budget_exceeded(self) -> bool:
        return (
            self.time_budget_s is not None
            and time.time() - self.start_time > self.time_budget_s
        )

    def exhausted(self) -> bool:
        """No further trials will ever be created."""
        return (
            self.searcher_exhausted
            or self.next_index >= self.num_samples
            or self.budget_exceeded()
        )

    def create_trial(self, **trial_kwargs) -> Optional[Trial]:
        """Sample the next config; returns the new PENDING trial or None."""
        if self.exhausted():
            return None
        config = self.searcher.suggest(self.next_index)
        if config is None:
            self.searcher_exhausted = True
            return None
        if self.config_overlay:
            config = {**self.config_overlay, **config}
        trial = Trial(
            trial_id=f"trial_{self.next_index:05d}", config=config, **trial_kwargs
        )
        self.next_index += 1
        self.trials.append(trial)
        self.by_id[trial.trial_id] = trial
        self.pending.append(trial)
        self.scheduler.on_trial_add(trial)
        if self.journal is not None:
            # WAL: the create decision (searcher suggestion consumed, trial
            # registered with the scheduler) is durable before its first
            # external effect (params.json) — a crash here resumes with the
            # trial recreated from the journaled config.
            self.journal.record_create(
                trial.trial_id, dict(config), self._snapshot()
            )
        self.store.write_params(trial)
        return trial

    def restore_experiment(self, resources=None) -> Dict[str, int]:
        """Resume an interrupted experiment from its directory (Ray's
        ``tune.run(resume=True)`` semantics, which the reference relied on
        implicitly by re-running its driver against the same ``local_dir``).

        For every persisted trial: rebuild the Trial from params.json +
        result.jsonl, replay its metric stream through the scheduler and
        searcher (rung tables and model-based search see the full history;
        nothing is re-persisted), then either keep it finished
        (TERMINATED/ERROR) or requeue it from its newest checkpoint
        (PENDING/RUNNING/PAUSED at the interruption). Sampling continues
        afterwards until ``num_samples``.
        """
        from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
        from distributed_machine_learning_tpu.tune.experiment import (
            iter_trial_records,
        )

        counts = {"finished": 0, "requeued": 0}
        for entry, config, records, meta in iter_trial_records(self.store.root):
            kwargs = {"resources": resources} if resources is not None else {}
            trial = Trial(trial_id=entry, config=config, **kwargs)
            self.trials.append(trial)
            self.by_id[entry] = trial
            try:
                self.next_index = max(
                    self.next_index, int(entry.rsplit("_", 1)[-1]) + 1
                )
            except ValueError:
                self.next_index = max(self.next_index, len(self.trials))
            self.scheduler.on_trial_add(trial)

            # A trial ABSENT from the state file was mid-flight when the
            # driver died (state snapshots are written on every completion,
            # so finished trials are always present): treat as interrupted,
            # never as finished — worst case a finished trial whose final
            # snapshot raced the crash re-runs from its last checkpoint.
            status = meta.get("status", "PENDING") if meta else "PENDING"
            finished = status in ("TERMINATED", "ERROR")
            # Start-of-run cleanup (safe here: no writer is live yet): a
            # sharded save the dead driver left half-written is deleted, so
            # find_latest below only ever names restorable generations.
            try:
                ckpt_lib.cleanup_uncommitted(
                    self.store.checkpoint_dir(trial), log=self.log
                )
            except Exception as exc:  # noqa: BLE001 - cleanup is best-effort
                self.log(f"uncommitted-checkpoint cleanup failed: {exc!r}")
            ck_path, ck_it = ckpt_lib.find_latest_checkpoint(
                self.store.checkpoint_dir(trial)
            )
            if not finished:
                # The re-run re-reports everything after the restore point;
                # drop the replayed tail past the checkpoint so the result
                # stream (and searcher observations) hold each epoch once —
                # on disk too, or the orphan tail would duplicate there.
                kept = [
                    r for r in records
                    if int(r.get("training_iteration", 0)) <= ck_it
                ]
                if len(kept) < len(records):
                    import json
                    import os

                    path = os.path.join(
                        self.store.trial_dir(trial), "result.jsonl"
                    )
                    with open(path, "w") as f:
                        for r in kept:
                            f.write(json.dumps(r) + "\n")
                records = kept

            # Replay: config snapshot guards against schedulers that mutate
            # on REQUEUE decisions during replay (PBT exploit) — replay must
            # only rebuild observer state, not re-run decisions.
            config_snapshot = dict(trial.config)
            for rec in records:
                trial.results.append(rec)
                trial.reports_since_restart += 1
                self.scheduler.on_trial_result(trial, rec)
                self.searcher.on_trial_result(
                    entry, config_snapshot, rec, self.metric, self.mode
                )
                if self.stop_rules is not None and callable(self.stop_rules):
                    # Warm STATEFUL stoppers (plateau windows/counters) with
                    # the replayed history; the returned decision is ignored
                    # — replay rebuilds observer state, it never re-decides.
                    stop_hit(self.stop_rules, trial.trial_id, rec)
            trial.config = config_snapshot
            # Clear anything replayed scheduler decisions left behind.
            trial._requeue_on_complete = False
            trial.restore_path = None
            trial.restore_base = 0
            trial.reports_since_restart = len(trial.results)
            if ck_path:
                trial.latest_checkpoint = ck_path
                trial.latest_checkpoint_iteration = ck_it

            if finished:
                trial.error = (meta or {}).get("error")
                self.finish(trial, TrialStatus(status))
                if status == "ERROR":
                    self.scheduler.on_trial_error(trial)
                counts["finished"] += 1
            else:
                # Interrupted mid-flight: rewind to the newest checkpoint
                # (training_iteration = restore_base once requeued).
                if ck_path:
                    trial.restore_path = ck_path
                    trial.restore_base = ck_it
                self.requeue(trial)
                counts["requeued"] += 1
        # Searchers with suggest-side state (GridSearch's cursor) advance
        # past the prefix of the space the prior run already proposed.
        self.searcher.fast_forward(self.next_index)
        return counts

    def restore_from_journal(self, replay, resources=None) -> Dict[str, int]:
        """Resume from the write-ahead log (``resume="auto"``): restore the
        journaled searcher/scheduler snapshot instead of replaying metric
        streams through their hooks, so the restored decision state is
        BIT-IDENTICAL to the moment of the last journaled decision — not a
        reconstruction of it.

        ``replay`` is a :class:`tune.journal.ReplayState`.  Ordering is
        load-bearing: (1) every live trial is rebuilt and registered via
        ``on_trial_add`` (PBT's live-ref table, ASHA's rung defaults);
        (2) THEN ``restore_state`` overwrites the defaults with the
        journaled snapshot; (3) trials are disposed — journaled-terminal
        trials get their status set directly (completion hooks already ran
        and are inside the snapshot), a trial whose watermark decision was
        "stop" is finished NOW (the decision was journaled but the crash
        ate its effect), everything else requeues from its newest valid
        checkpoint at-or-below the journaled report watermark, with
        re-reports below the watermark suppressed (exactly-once epoch
        accounting — see :meth:`process_result`).
        """
        from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib
        from distributed_machine_learning_tpu.tune.experiment import (
            iter_trial_records,
        )

        counts = {"finished": 0, "requeued": 0, "suppress_windows": 0}
        kwargs = {"resources": resources} if resources is not None else {}
        on_disk: Dict[str, Any] = {}
        for entry, config, records, _meta in iter_trial_records(
            self.store.root
        ):
            on_disk[entry] = (config, records)
        # Union: a journaled create whose params.json never landed (crash
        # inside the create→write_params window) is recreated from the
        # journaled config.
        trial_ids = sorted(set(on_disk) | set(replay.trials))
        pending_disposal = []
        for entry in trial_ids:
            jt = replay.trials.get(entry)
            if jt is not None and jt["config"] is None and entry not in on_disk:
                continue  # journal mentions it but holds no config (torn)
            config, records = on_disk.get(entry) or (
                dict(jt["config"]), []
            )
            trial = Trial(trial_id=entry, config=config, **kwargs)
            self.trials.append(trial)
            self.by_id[entry] = trial
            try:
                self.next_index = max(
                    self.next_index, int(entry.rsplit("_", 1)[-1]) + 1
                )
            except ValueError:
                self.next_index = max(self.next_index, len(self.trials))
            self.scheduler.on_trial_add(trial)
            if entry not in on_disk:
                self.store.write_params(trial)  # re-run the eaten effect

            watermark = int(jt["reported_through"]) if jt else 0
            terminal = jt["terminal"] if jt else None
            # Disk results past the journaled watermark are evidence of
            # work whose report never became a decision (crash between
            # append_result and the journal append): truncate, so the
            # re-reported epoch lands exactly once on disk too.
            if terminal is None:
                kept = [
                    r for r in records
                    if int(r.get("training_iteration", 0)) <= watermark
                ]
                if len(kept) < len(records):
                    import json
                    import os

                    path = os.path.join(
                        self.store.trial_dir(trial), "result.jsonl"
                    )
                    with open(path, "w") as f:
                        for r in kept:
                            f.write(json.dumps(r) + "\n")
                records = kept
            for rec in records:
                trial.results.append(rec)
                if self.stop_rules is not None and callable(self.stop_rules):
                    # Warm STATEFUL stoppers only; scheduler/searcher state
                    # comes from the snapshot, not from replaying hooks.
                    stop_hit(self.stop_rules, trial.trial_id, rec)
            trial.reports_since_restart = len(trial.results)
            pending_disposal.append((trial, jt, watermark))

        # The journaled snapshot is authoritative: it overwrites the
        # defaults on_trial_add just installed (ASHA rung cursors, PBT
        # history) and the searcher's model/cursor state.  next_index from
        # the snapshot covers creates whose params.json landed but whose
        # ids don't parse.
        snap = replay.snapshot
        if snap:
            self.searcher.restore_state(snap.get("searcher") or {})
            self.scheduler.restore_state(snap.get("scheduler") or {})
            self.next_index = max(
                self.next_index, int(snap.get("next_index", 0))
            )
        else:
            self.searcher.fast_forward(self.next_index)

        for trial, jt, watermark in pending_disposal:
            terminal = jt["terminal"] if jt else None
            if terminal is not None:
                # Completion hooks ran before the complete record was
                # journaled and their mutations are inside the snapshot:
                # set the status directly, never re-run finish().
                trial.status = TrialStatus(terminal.get("status", "TERMINATED"))
                trial.error = terminal.get("error")
                trial.finished_at = time.time()
                counts["finished"] += 1
                continue
            decision = jt["decision_at_watermark"] if jt else None
            if decision == "stop":
                # The stop decision is durable; the crash ate its effect.
                # finish() now runs the completion hooks exactly once (the
                # control run would have run them at this point too) and
                # journals the complete record.
                self.finish(trial, TrialStatus.TERMINATED)
                counts["finished"] += 1
                continue
            ck_dir = self.store.checkpoint_dir(trial)
            try:
                ckpt_lib.cleanup_uncommitted(ck_dir, log=self.log)
                # Checkpoints past the watermark hold epochs whose reports
                # never became decisions; quarantine so no later fallback
                # can resurrect them (the requeue_lost discipline).
                ckpt_lib.quarantine_unreported(
                    ck_dir, watermark, tag="head", log=self.log
                )
            except Exception as exc:  # noqa: BLE001 - best-effort hygiene
                self.log(f"checkpoint hygiene failed for "
                         f"{trial.trial_id}: {exc!r}")
            last_requeue = jt["last_requeue"] if jt else None
            trial._requeue_on_complete = False
            if last_requeue is not None:
                # A journaled PBT exploit owns this trial's current config
                # and restore target (its in-memory config died with the
                # head; params.json still holds the original).  Re-apply
                # the exploit verbatim — re-reports up to the watermark are
                # suppressed, so re-running the donor window is wasted
                # compute, never duplicate accounting.
                trial.config = dict(last_requeue.get("config") or trial.config)
                trial.restore_path = last_requeue.get("restore_path")
                trial.restore_base = int(last_requeue.get("restore_base") or 0)
            else:
                ck_path, ck_it = ckpt_lib.newest_valid_checkpoint(
                    ck_dir, max_iteration=watermark
                )
                if ck_path:
                    trial.restore_path = ck_path
                    trial.restore_base = ck_it
                    trial.latest_checkpoint = ck_path
                    trial.latest_checkpoint_iteration = ck_it
                else:
                    trial.restore_path = None
                    trial.restore_base = 0
            if trial.restore_base < watermark:
                self._suppress[trial.trial_id] = watermark
                counts["suppress_windows"] += 1
            self.requeue(trial)
            counts["requeued"] += 1

        if self.journal is not None:
            self.journal.record_replay(**counts)
        return counts

    # -- results -----------------------------------------------------------

    def process_result(
        self, trial: Trial, metrics: Dict[str, Any], extra: Optional[Dict] = None
    ) -> str:
        """Stamp + persist a result, run scheduler/searcher; returns
        "stop" or "continue" (REQUEUE is folded into stop + a flag consumed
        by :meth:`complete_trial`)."""
        metrics = dict(metrics)
        watermark = self._suppress.get(trial.trial_id)
        if watermark is not None:
            # Journal-resume duplicate window: this incarnation restored
            # from a checkpoint below the journaled report watermark, so it
            # re-reports epochs the control plane already observed.  The
            # iteration clock still advances (training_iteration must line
            # up when fresh reports start), but nothing is re-persisted,
            # re-observed, or re-decided — every such epoch was journaled
            # "continue" (a stop/requeue watermark is resolved at restore).
            trial.reports_since_restart += 1
            it = trial.training_iteration
            if it <= watermark:
                self.duplicate_reports_suppressed += 1
                if it == watermark:
                    del self._suppress[trial.trial_id]
                return "continue"
            # Already past the watermark (sparse reporting): fall through
            # to the normal path, undoing the early increment.
            del self._suppress[trial.trial_id]
            trial.reports_since_restart -= 1
        trial.reports_since_restart += 1
        metrics.setdefault("training_iteration", trial.training_iteration)
        metrics["trial_id"] = trial.trial_id
        metrics["timestamp"] = time.time()
        metrics["time_total_s"] = trial.runtime_s()
        if extra:
            metrics.update(extra)
        trial.results.append(metrics)
        with obs.span("runner.store_append"):
            self.store.append_result(trial, metrics)
            self._prune_checkpoints(trial)

        # Snapshot before the scheduler runs: PBT mutates trial.config in
        # place on REQUEUE, and the searcher must see the config that
        # actually produced these metrics.
        reported_config = dict(trial.config)
        with obs.span("runner.scheduler"):
            decision = self.scheduler.on_trial_result(trial, metrics)
        with obs.span("runner.searcher"):
            self.searcher.on_trial_result(
                trial.trial_id, reported_config, metrics, self.metric,
                self.mode,
            )
        if self.stop_rules:
            # Dict of key->threshold, or a callable/Stopper
            # (tune/stoppers.py) judging this trial's own trajectory.
            if stop_hit(self.stop_rules, trial.trial_id, metrics):
                decision = STOP if decision == CONTINUE else decision
        if trial.stop_requested or self.budget_exceeded():
            decision = STOP
        if (
            self.time_limit_per_trial_s is not None
            and trial.incarnation_runtime_s() > self.time_limit_per_trial_s
            and decision == CONTINUE
        ):
            # Soft per-trial time limit: stop at the report boundary.  Trials
            # that never reach a report boundary are reaped by the runner's
            # hard-kill path (process executor).  Measured per incarnation so
            # a retried trial gets a fresh clock.
            self.log(
                f"{trial.trial_id} hit time limit "
                f"({trial.incarnation_runtime_s():.0f}s); stopping"
            )
            decision = STOP
        requeued = decision == REQUEUE
        if requeued:
            trial._requeue_on_complete = True
            decision = STOP
        if self.journal is not None:
            # WAL: scheduler/searcher/stopper mutations are all in; journal
            # the decision (with the post-mutation snapshot) before it is
            # returned to the executor.  A crash after the append replays
            # to this exact state and re-applies the decision at resume.
            requeue_payload = None
            if requeued:
                # PBT exploit: the scheduler rewrote config/restore target
                # in place.  Journaled so resume re-applies the exploit even
                # if the complete event (which performs the requeue) never
                # got processed.
                requeue_payload = {
                    "config": dict(trial.config),
                    "restore_path": trial.restore_path,
                    "restore_base": trial.restore_base,
                }
            value = metrics.get(self.metric)
            with obs.span("runner.journal"):
                self.journal.record_report(
                    trial.trial_id,
                    int(metrics.get("training_iteration",
                                    trial.training_iteration)),
                    "requeue" if requeued
                    else ("stop" if decision == STOP else "continue"),
                    float(value)
                    if isinstance(value, (int, float)) else None,
                    self._snapshot(),
                    requeue=requeue_payload,
                )
        return "stop" if decision == STOP else "continue"

    def final_prune(self) -> None:
        """End-of-run retention pass over every trial. Call AFTER the
        executor's writer has drained (join_all): writes that landed after
        a trial's last in-run prune (the depth-2 pipeline keeps up to 2 in
        flight) converge to exactly ``keep_checkpoints_num`` on disk."""
        for trial in self.trials:
            self._prune_checkpoints(trial)

    def _prune_checkpoints(self, trial: Trial):
        """Retention: keep the last k checkpoints of ``trial``, never deleting
        one that any trial's pending restore (PBT exploit / retry) points at.

        Runs on the single lifecycle thread, so the protect set is consistent
        with every REQUEUE decision made so far."""
        if self.keep_checkpoints_num <= 0 or not trial.latest_checkpoint:
            return
        from distributed_machine_learning_tpu.tune import checkpoint as ckpt_lib

        protected = {t.restore_path for t in self.trials if t.restore_path}
        protected.add(trial.latest_checkpoint)
        directory = self.store.checkpoint_dir(trial)
        try:
            # latest may still be in the async writer's queue: the newest k
            # DURABLE files are retained against it (transient overshoot up
            # to k + the executor's write-pipeline depth while writes land;
            # later prunes and final_prune converge back to k).
            ckpt_lib.prune_checkpoints(
                directory, self.keep_checkpoints_num, protect=protected,
                pending_latest=trial.latest_checkpoint,
            )
        except Exception as e:  # retention must never kill a run
            self.log(f"checkpoint pruning failed for {trial.trial_id}: {e}")

    # -- terminal events ---------------------------------------------------

    def complete_trial(self, trial: Trial) -> bool:
        """Trial finished cleanly. Returns True if it was requeued (PBT)."""
        if getattr(trial, "_requeue_on_complete", False):
            trial._requeue_on_complete = False
            self.requeue(trial)
            return True
        self.finish(trial, TrialStatus.TERMINATED)
        return False

    def fail_trial(self, trial: Trial, why: str) -> bool:
        """Trial errored/preempted. Returns True if it will be retried."""
        trial.num_failures += 1
        # A PBT-style REQUEUE may be pending when the failure lands; the
        # trial is being requeued NOW, so consume the flag — otherwise its
        # eventual genuine completion would trigger a spurious extra re-run.
        pbt_requeue = getattr(trial, "_requeue_on_complete", False)
        trial._requeue_on_complete = False
        if trial.num_failures <= self.max_failures:
            if pbt_requeue and trial.restore_path:
                # A scheduler-chosen restore target (PBT exploit pointing at a
                # DONOR's checkpoint) is being applied right now — keep it;
                # the scheduler already set restore_base.
                pass
            elif (
                trial.latest_checkpoint
                and trial.latest_checkpoint_iteration >= trial.restore_base
            ):
                # Most-advanced restore point available: the trial's own
                # newest checkpoint — unless the current incarnation was
                # seeded by a donor exploit it hasn't checkpointed past yet
                # (own checkpoint older than restore_base), in which case
                # overwriting would silently undo the exploit's weights.
                trial.restore_path = trial.latest_checkpoint
                trial.restore_base = trial.latest_checkpoint_iteration
            elif not trial.restore_path:
                trial.restore_base = 0
            # else: keep the seed restore target (donor / previous retry).
            self.log(
                f"{trial.trial_id} failed "
                f"({trial.num_failures}/{self.max_failures}): {why.splitlines()[-1] if why else why}; retrying"
                + (" from checkpoint" if trial.restore_path else "")
            )
            if self.journal is not None:
                self.journal.record_error(
                    trial.trial_id, True, self._snapshot()
                )
            self.requeue(trial)
            return True
        trial.error = why
        self.finish(trial, TrialStatus.ERROR)
        self.scheduler.on_trial_error(trial)
        return False

    def finish(self, trial: Trial, status: TrialStatus):
        trial.status = status
        trial.finished_at = time.time()
        if status == TrialStatus.TERMINATED:
            self.searcher.on_trial_complete(
                trial.trial_id, trial.config, trial.last_result, self.metric, self.mode
            )
        else:
            # Errored trials complete with result=None: model-based
            # searchers skip the observation (their None-score guard), but
            # WRAPPING searchers still see the completion — a Repeater
            # group with a crashed member must dispatch its mean instead of
            # stalling forever on a report that will never come.
            self.searcher.on_trial_complete(
                trial.trial_id, trial.config, None, self.metric, self.mode
            )
        self.scheduler.on_trial_complete(trial)
        if self.journal is not None:
            # Journaled AFTER the completion hooks mutate searcher/scheduler
            # state, so the snapshot is the post-completion decision state
            # (a resume that finds this record sets status directly — the
            # hooks must not run twice).
            self.journal.record_complete(
                trial.trial_id, status.value, self._snapshot(),
                error=trial.error,
            )

    def requeue(self, trial: Trial):
        trial.status = TrialStatus.PENDING
        trial.reports_since_restart = 0
        self.pending.append(trial)

    def mark_running(self, trial: Trial, worker: Optional[str] = None):
        if self.journal is not None:
            # WAL: dispatch journaled before the launch frame/thread exists,
            # so resume knows this trial was in flight (no state snapshot —
            # dispatch decides nothing).
            self.journal.record_dispatch(trial.trial_id, worker=worker)
        trial.status = TrialStatus.RUNNING
        now = time.time()
        trial.started_at = trial.started_at or now
        trial.restarted_at = now
        trial.incarnation += 1
        trial.stop_requested = False

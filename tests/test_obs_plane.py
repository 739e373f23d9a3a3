"""The observability plane (obs/, ISSUE 13): tracing across thread /
process / cluster boundaries, the always-on flight recorder and its
dump-on-stall path, the unified metrics registry + head aggregation,
chaos coverage of the exporters, the trace CLI, and the disabled-path
overhead guard."""

import glob
import json
import os
import sys
import threading
import time

import pytest

from distributed_machine_learning_tpu import chaos, obs, tune
from distributed_machine_learning_tpu.tune.cluster import (
    run_distributed,
    start_local_workers,
)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _fresh_obs_state():
    """Each test starts with tracing off and no ambient dump dir."""
    obs.shutdown()
    obs.set_dump_dir(None)
    yield
    obs.shutdown()
    obs.set_dump_dir(None)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class _Fam:
    def __init__(self):
        self.hits = 0

    def snapshot(self):
        return {"hits": self.hits}


def test_registry_families_and_counters():
    reg = obs.get_registry()
    fam = _Fam()
    reg.register_family("obs_test_fam", fam)
    try:
        fam.hits = 3
        snap = reg.snapshot()
        assert snap["families"]["obs_test_fam"] == {"hits": 3}
        base = reg.counters_snapshot()
        reg.add("obs_test_counter", 2)
        assert reg.delta_since(base)["obs_test_counter"] == 2
        flat = reg.scalar_snapshot()
        assert flat["obs_test_fam/hits"] == 3
    finally:
        reg.unregister_family("obs_test_fam")
    assert "obs_test_fam" not in reg.families()


def test_registry_broken_family_is_counted_not_fatal():
    reg = obs.get_registry()
    reg.register_family("obs_broken_fam", lambda: 1 / 0)
    try:
        before = reg.get("family_errors")
        snap = reg.snapshot()
        assert "obs_broken_fam" not in snap["families"]
        assert reg.get("family_errors") == before + 1
    finally:
        reg.unregister_family("obs_broken_fam")


def test_registry_stale_unregister_does_not_evict_newer():
    reg = obs.get_registry()
    old, new = _Fam(), _Fam()
    reg.register_family("obs_gen_fam", old)
    reg.register_family("obs_gen_fam", new)  # new run re-registers
    reg.unregister_family("obs_gen_fam", old)  # old run's teardown
    assert "obs_gen_fam" in reg.families()
    reg.unregister_family("obs_gen_fam", new)


def test_builtin_families_are_registered():
    # The six-family migration: the process singletons registered at
    # import; per-run families (liveness, pbt, injected_faults) register
    # when their owners exist.
    import distributed_machine_learning_tpu.data.pipeline  # noqa: F401

    fams = obs.get_registry().families()
    for name in ("checkpoint", "compile", "host_input"):
        assert name in fams, fams
    with chaos.active(chaos.FaultPlan(seed=1)):
        assert "injected_faults" in obs.get_registry().families()
    assert "injected_faults" not in obs.get_registry().families()


def test_aggregate_scalars_sums_across_sources():
    agg = obs.aggregate_scalars({
        "w1": {"a/x": 1, "a/y": 2.5, "skip": "str"},
        "w2": {"a/x": 3},
    })
    assert agg == {"a/x": 4, "a/y": 2.5}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_ordered():
    rec = obs.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("tick", {"i": i})
    events = rec.events()
    assert len(events) == 8
    assert [e["detail"]["i"] for e in events] == list(range(12, 20))


def test_flight_mirror_survives_without_dump(tmp_path):
    mirror = str(tmp_path / "mirror.jsonl")
    rec = obs.FlightRecorder(capacity=4, mirror_path=mirror)
    rec.record("phase", {"name": "claim"})
    rec.record("phase", {"name": "execute"})
    lines = [json.loads(ln) for ln in open(mirror) if ln.strip()]
    assert [ln["detail"]["name"] for ln in lines] == ["claim", "execute"]


def test_dump_includes_ring_spans_and_registry(tmp_path):
    obs.configure(trace_dir=str(tmp_path / "tr"), dump_dir=str(tmp_path))
    obs.event("before_dump", {"k": 1})
    with obs.span("open_phase", {"trial_id": "t0"}):
        path = obs.dump_flight_recorder("unit", extra={"why": "test"})
    assert path and os.path.exists(path)
    payload = json.load(open(path))
    assert payload["reason"] == "unit"
    assert any(e["kind"] == "before_dump" for e in payload["events"])
    assert any(
        stack and stack[-1]["name"] == "open_phase"
        for stack in payload["span_stacks"].values()
    )
    assert "families" in payload["registry"]
    assert payload["extra"] == {"why": "test"}


def test_dump_without_destination_is_noop():
    assert obs.dump_flight_recorder("nowhere") is None


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------


def test_span_nesting_and_context(tmp_path):
    obs.configure(trace_dir=str(tmp_path), label="unit")
    with obs.span("outer", {"trial_id": "t1"}) as outer:
        assert obs.current_context() == outer.context
        with obs.span("inner") as inner:
            assert inner.parent_id == outer.span_id
            assert inner.trace_id == outer.trace_id
    obs.flush()
    records = obs.get_tracer().records()
    by_name = {r["name"]: r for r in records}
    assert by_name["inner"]["args"]["parent_id"] == (
        by_name["outer"]["args"]["span_id"]
    )
    # inner landed first (ended first), both in the JSONL sink
    lines = open(obs.get_tracer().path).read().strip().splitlines()
    assert len(lines) == 2


def test_explicit_parent_crosses_threads(tmp_path):
    obs.configure(trace_dir=str(tmp_path), label="unit")
    with obs.span("request") as req:
        ctx = obs.current_context()

        def worker():
            with obs.span("flush", parent=ctx):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    records = {r["name"]: r for r in obs.get_tracer().records()}
    assert records["flush"]["args"]["parent_id"] == req.span_id


def test_exception_marks_span_and_unwinds(tmp_path):
    obs.configure(trace_dir=str(tmp_path), label="unit")
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError("boom")
    rec = obs.get_tracer().records()[-1]
    assert rec["name"] == "failing" and rec["args"]["error"] == "ValueError"
    assert obs.current_context() is None  # stack unwound


def test_merge_and_chrome_schema(tmp_path):
    obs.configure(trace_dir=str(tmp_path), label="unit")
    with obs.span("a", {"trial_id": "t"}):
        obs.add_complete("compile.backend", 0.001)
    obs.flush()
    out = obs.merge_trace_dir(str(tmp_path))
    data = json.load(open(out))
    assert set(data) >= {"traceEvents", "displayTimeUnit"}
    complete = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"a", "compile.backend"}
    for e in complete:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e, e
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # metadata events label process lanes
    assert any(e["ph"] == "M" for e in data["traceEvents"])


# ---------------------------------------------------------------------------
# disabled-path overhead guard (the always-on-instrumentation contract)
# ---------------------------------------------------------------------------


def test_disabled_span_perf_guard():
    assert not obs.tracing_enabled()
    # The bridged path: jax is imported (conftest), so every span asks the
    # profiler whether a session runs before it hands back the singleton.
    from distributed_machine_learning_tpu.obs import trace as obs_trace

    assert "jax.profiler" in sys.modules
    assert obs_trace._find_annotation() is sys.modules[
        "jax.profiler"
    ].TraceAnnotation
    assert not obs_trace._find_annotation().is_enabled()
    # Best of three: CI machines stutter; a regression shifts ALL runs.
    best = min(
        (obs.disabled_path_overhead(iters=50_000) for _ in range(3)),
        key=lambda r: r["ns_per_span"],
    )
    strict = os.environ.get("DML_OBS_PERF_GUARD") == "1"
    ns_budget = 800.0 if strict else 1500.0
    assert best["ns_per_span"] <= ns_budget, best
    # "allocates nothing per span": net allocated blocks must not scale
    # with the span count (tiny constant jitter from interned state ok).
    assert best["net_blocks"] <= 16, best


# ---------------------------------------------------------------------------
# the bridge to the profiler's host plane (ISSUE 27)
# ---------------------------------------------------------------------------


@pytest.fixture
def no_compile_cache():
    """A profiler session beside jax's persistent compilation cache: off
    around these tests, as the on-chip-measurement guide says."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _plane_lines(trace_dir):
    """The ``dml:`` events of a capture, one list per host line (thread):
    ``(name, start_ns, end_ns, stats)`` in order of start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                ((e.name, e.start_ns, e.start_ns + e.duration_ns,
                  dict(e.stats))
                 for e in line.events if e.name.startswith("dml:")),
                key=lambda ev: (ev[1], -ev[2]),
            )
            if evs:
                lines.append(evs)
    return lines


def _named(lines, name):
    return [e for line in lines for e in line if e[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _spans_on_two_threads():
    with obs.span("plane.outer", {"trial_id": "t,1#", "n": 3,
                                  "skipped": [1, 2]}) as outer:
        with obs.span("plane.inner") as inner:
            inner.set("late", 7)
        worker = threading.Thread(
            target=lambda: obs.span("plane.worker", {"w": 1.5}).end()
        )
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
    return outer, inner


@pytest.mark.parametrize("session", [False, True], ids=["nosession", "session"])
@pytest.mark.parametrize("tracer", [False, True], ids=["notracer", "tracer"])
def test_span_lands_in_the_profilers_host_plane(tmp_path, no_compile_cache,
                                                 tracer, session):
    import jax

    if tracer:
        obs.configure(trace_dir=str(tmp_path / "spans"), label="t")
    if not session:
        # Nothing listens on the plane: the singleton (or a tracer-only
        # span), and a capture started afterwards holds none of it.
        if not tracer:
            assert obs.span("a") is obs.span("b")
        outer, inner = _spans_on_two_threads()
        assert getattr(outer, "_ann", None) is None
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        if session:
            outer, inner = _spans_on_two_threads()
    finally:
        jax.profiler.stop_trace()
    lines = _plane_lines(str(tmp_path / "prof"))
    if tracer:
        names = {r["name"] for r in obs.get_tracer().records()}
        assert {"plane.outer", "plane.inner", "plane.worker"} <= names
    if not session:
        assert lines == []
        return
    assert len(lines) == 2  # one line a thread
    main = next(li for li in lines if _named([li], "dml:plane.outer"))
    (other,) = [li for li in lines if li is not main]
    assert [e[0] for e in main] == ["dml:plane.outer", "dml:plane.inner"]
    assert [e[0] for e in other] == ["dml:plane.worker"]
    o, i = main
    assert _inside(i, o)
    # scalar attrs ride as stats (what would end a value early replaced),
    # a list does not; an attr set while the span is open arrives too
    assert o[3]["trial_id"] == "t_1_" and o[3]["n"] == 3
    assert "skipped" not in o[3]
    assert i[3]["late"] == 7
    assert other[0][3]["w"] == 1.5
    if tracer:
        assert o[3]["span_id"] == outer.span_id
        assert i[3]["parent_id"] == outer.span_id
        assert outer.context == (outer.trace_id, outer.span_id)
    else:
        assert "span_id" not in o[3] and outer.context is None


def test_train_run_spans_on_the_profilers_clock(tmp_results,
                                                no_compile_cache):
    """tune.run of train_regressor, 2 epochs, a checkpoint each, under a
    profiler session: the report path's spans, where and in what order."""
    import jax

    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=64, seq_len=8, num_features=4
    )
    prof = os.path.join(tmp_results, "plane_train_prof")
    jax.profiler.start_trace(prof)
    try:
        tune.run(
            tune.with_parameters(
                tune.train_regressor, train_data=train, val_data=val
            ),
            {"model": "simple_transformer", "d_model": 16, "num_heads": 2,
             "num_layers": 1, "learning_rate": 1e-3, "num_epochs": 2,
             "batch_size": 16, "max_seq_length": 16},
            metric="validation_loss", num_samples=1,
            storage_path=tmp_results, name="plane_train", verbose=0,
        )
    finally:
        jax.profiler.stop_trace()
    lines = _plane_lines(prof)
    epochs = _named(lines, "dml:epoch")
    reports = _named(lines, "dml:report")
    decides = _named(lines, "dml:runner.process_result")
    saves = _named(lines, "dml:ckpt.save")
    assert [len(x) for x in (epochs, reports, decides, saves)] == [2, 2, 2, 2]
    (trial,) = _named(lines, "dml:trial")
    (trial_line,) = [li for li in lines if _named([li], "dml:epoch")]
    assert _named([trial_line], "dml:report") == reports
    assert not _named([trial_line], "dml:ckpt.save")  # the writer's thread
    assert not _named([trial_line], "dml:runner.process_result")
    for ep, rep, dec in zip(epochs, reports, decides):
        assert ep[2] <= rep[1]  # the report after its epoch
        assert _inside(ep, trial) and _inside(rep, trial)
        assert _inside(dec, rep)  # the runner decides while the trial waits
        assert rep[3]["iteration"] == dec[3]["iteration"]
    assert [r[3]["iteration"] for r in reports] == [1, 2]
    writes = _named(lines, "dml:ckpt.write")
    reads = _named(lines, "dml:ckpt.device_get")
    for save, write in zip(saves, writes):
        assert save[3]["format"] == "msgpack" and save[3]["streamed"]
        assert _inside(write, save)
        # the payload goes from the leaves to the file: its bytes are the
        # write's, and the writer's device leaves are read inside it
        assert write[3]["bytes"] == save[3]["bytes"] > 0
        assert write[3]["chunks"] >= 1 and write[3]["serialize_s"] >= 0
        assert [r for r in reads if _inside(r, write)]
    assert all(any(_inside(r, w) for w in writes) for r in reads)
    assert not _named(lines, "dml:ckpt.serialize")
    for name in ("dml:run.setup", "dml:run.teardown", "dml:trial.setup",
                 "dml:trial.build", "dml:trial.init_or_restore"):
        assert len(_named(lines, name)) == 1, name
    for name in ("dml:epoch.dispatch", "dml:epoch.readback",
                 "dml:report.ckpt_snapshot", "dml:report.decide_wait",
                 "dml:ckpt.write", "dml:runner.store_append",
                 "dml:runner.scheduler", "dml:runner.searcher",
                 "dml:runner.callbacks"):
        assert len(_named(lines, name)) == 2, name


def test_vectorized_run_spans_on_the_profilers_clock(tmp_results,
                                                     no_compile_cache):
    """run_vectorized under ASHA: one vec.emit a vec.dispatch, and their
    ``results`` add up to the records the trials hold."""
    import jax

    from distributed_machine_learning_tpu.data import dummy_regression_data

    train, val = dummy_regression_data(
        num_samples=64, seq_len=8, num_features=4
    )
    prof = os.path.join(tmp_results, "plane_vec_prof")
    jax.profiler.start_trace(prof)
    try:
        analysis = tune.run_vectorized(
            {"model": "simple_transformer", "d_model": 16, "num_heads": 2,
             "num_layers": 1, "learning_rate": tune.loguniform(1e-4, 1e-2),
             "num_epochs": 4, "batch_size": 16, "max_seq_length": 16},
            train_data=train, val_data=val, metric="validation_loss",
            num_samples=8, max_batch_trials=8, storage_path=tmp_results,
            name="plane_vec", verbose=0, epochs_per_dispatch=1,
            scheduler=tune.ASHAScheduler(
                max_t=4, grace_period=1, reduction_factor=2
            ),
        )
    finally:
        jax.profiler.stop_trace()
    lines = _plane_lines(prof)
    assert len(lines) == 1  # the caller's thread does it all
    dispatches = _named(lines, "dml:vec.dispatch")
    emits = _named(lines, "dml:vec.emit")
    assert len(dispatches) == len(emits) >= 2
    for disp, emit in zip(dispatches, emits):
        assert disp[2] <= emit[1]
    records = sum(len(t.results) for t in analysis.trials)
    assert sum(e[3]["results"] for e in emits) == records
    assert sum(e[3]["stopped"] for e in emits) == 8  # ASHA ended them all
    for key in ("store_s", "callbacks_s", "scheduler_s", "searcher_s"):
        assert all(e[3][key] >= 0 for e in emits)
    (run_span,) = _named(lines, "dml:vec.run")
    assert run_span[3]["name"] == "plane_vec"
    (setup,) = _named(lines, "dml:vec.setup")
    (suggest,) = _named(lines, "dml:vec.suggest")
    assert suggest[3]["trials"] == 8 and _inside(suggest, setup)
    assert _inside(_named(lines, "dml:vec.program")[0], setup)
    assert _inside(_named(lines, "dml:vec.init")[0], setup)
    assert setup[2] <= dispatches[0][1]
    for disp in dispatches:
        (launch,) = [e for e in _named(lines, "dml:vec.launch")
                     if _inside(e, disp)]
        (sync,) = [e for e in _named(lines, "dml:vec.sync")
                   if _inside(e, disp)]
        assert launch[2] <= sync[1]
    (teardown,) = _named(lines, "dml:vec.teardown")
    assert emits[-1][2] <= teardown[1] and _inside(teardown, run_span)


# ---------------------------------------------------------------------------
# e2e: thread + process executors (acceptance criterion)
# ---------------------------------------------------------------------------


def _ckpt_trainable(config):
    for _ in range(2):
        tune.report(loss=config["x"] ** 2, checkpoint={"w": [1.0]})


def _assert_trial_trace(root, expect_multi_pid):
    data = json.load(open(os.path.join(root, "trace", "trace.json")))
    evs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    trace_ids = {e["args"].get("trace_id") for e in evs}
    assert len(trace_ids) == 1, trace_ids  # consistent across processes
    names = {e["name"] for e in evs}
    assert {"experiment", "trial.dispatch", "trial", "epoch",
            "ckpt.save"} <= names, names
    if expect_multi_pid:
        assert len({e["pid"] for e in evs}) >= 2
    exp = next(e for e in evs if e["name"] == "experiment")
    dispatch = {
        e["args"]["span_id"]: e for e in evs
        if e["name"] == "trial.dispatch"
    }
    trials = [e for e in evs if e["name"] == "trial"]
    assert len(dispatch) == 2 and len(trials) == 2
    for t in trials:
        parent = dispatch[t["args"]["parent_id"]]
        assert parent["args"]["parent_id"] == exp["args"]["span_id"]
        assert parent["args"]["trial_id"] == t["args"]["trial_id"]
    # epochs nest under their trial spans
    trial_ids = {t["args"]["span_id"] for t in trials}
    epochs = [e for e in evs if e["name"] == "epoch"]
    assert epochs and all(
        e["args"]["parent_id"] in trial_ids for e in epochs
    )
    return data


def test_traced_run_thread_executor_merges_chrome_trace(tmp_results):
    analysis = tune.run(
        _real_epoch_trainable, {"lr": tune.uniform(1e-4, 1e-2)},
        metric="loss", mode="min", num_samples=2,
        storage_path=tmp_results, name="obs_thread", verbose=0,
        trace=True,
    )
    root = os.path.join(tmp_results, "obs_thread")
    _assert_trial_trace(root, expect_multi_pid=False)
    state = json.load(open(os.path.join(root, "experiment_state.json")))
    assert state["obs"]["spans_recorded"] > 0
    assert state["obs"]["trace"].endswith("trace.json")
    assert analysis.best_config is not None
    # tracing is OFF again after the run
    assert not obs.tracing_enabled()


def _real_epoch_trainable(config):
    # Uses obs.span the way the built-in trainables do, so the e2e sees
    # driver->trial->epoch->ckpt spans without needing a jax model.
    for epoch in range(2):
        with obs.span("epoch", {"epoch": epoch}):
            time.sleep(0.01)
        tune.report(loss=config["lr"], checkpoint={"w": [1.0]})


def test_traced_run_process_executor_spans_cross_processes(tmp_results):
    tune.run(
        _real_epoch_trainable, {"lr": tune.uniform(1e-4, 1e-2)},
        metric="loss", mode="min", num_samples=2,
        storage_path=tmp_results, name="obs_proc", verbose=0,
        trace=True, trial_executor="process",
    )
    root = os.path.join(tmp_results, "obs_proc")
    _assert_trial_trace(root, expect_multi_pid=True)


# ---------------------------------------------------------------------------
# e2e: flight-recorder dump on stall names the hang site (acceptance)
# ---------------------------------------------------------------------------


def _hang_trainable(config):
    tune.report(loss=1.0)
    with obs.span("epoch", {"epoch": 1, "where": "hang_site"}):
        time.sleep(1.1)  # > deadline; no heartbeat — a silent dispatch
    tune.report(loss=0.5)


def test_stall_dumps_flight_recorder_with_hang_site(tmp_results):
    tune.run(
        _hang_trainable, {"x": tune.uniform(0, 1)},
        metric="loss", mode="min", num_samples=1,
        storage_path=tmp_results, name="obs_stall", verbose=0,
        trace=True, progress_deadline_s=0.3, progress_grace_s=0.2,
    )
    root = os.path.join(tmp_results, "obs_stall")
    dumps = glob.glob(os.path.join(root, "flightrec_*_stall_*.json"))
    assert dumps, os.listdir(root)
    payload = json.load(open(dumps[0]))
    # The tail of the dump carries the hang site: the stalled trial
    # thread's innermost open span is the epoch it hung inside.
    hang_stacks = [
        s for s in payload["span_stacks"].values()
        if s and s[-1]["name"] == "epoch"
        and s[-1]["attrs"].get("where") == "hang_site"
    ]
    assert hang_stacks, payload["span_stacks"]
    # ... and the ring shows the watchdog seeing the silence.
    kinds = [e["kind"] for e in payload["events"]]
    assert "watchdog_stall" in kinds
    state = json.load(open(os.path.join(root, "experiment_state.json")))
    assert state["liveness"]["stalls_detected"] >= 1
    assert state["obs"]["flight_dumps"] >= 1


# ---------------------------------------------------------------------------
# e2e: cluster dispatch carries the trace across the frame boundary
# ---------------------------------------------------------------------------


def _worker_env():
    keep = [
        p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
        if p
    ]
    return {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join([TESTS_DIR] + keep),
    }


def test_cluster_dispatch_trace_ids_and_head_aggregation(tmp_path):
    procs, addrs = start_local_workers(1, slots=2, env=_worker_env())
    try:
        run_distributed(
            "cluster_trainables:quadratic_trial",
            {"x": tune.uniform(0.0, 6.0), "epochs": 3},
            metric="loss", mode="min", num_samples=2,
            workers=addrs, storage_path=str(tmp_path), name="obs_cluster",
            verbose=0, trace=True,
        )
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()
    root = os.path.join(str(tmp_path), "obs_cluster")
    data = json.load(open(os.path.join(root, "trace", "trace.json")))
    evs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    assert len({e["args"].get("trace_id") for e in evs}) == 1
    assert len({e["pid"] for e in evs}) >= 2  # head + worker process
    dispatch = {
        e["args"]["span_id"]: e for e in evs
        if e["name"] == "trial.dispatch"
    }
    trials = [e for e in evs if e["name"] == "trial"]
    assert trials, sorted({e["name"] for e in evs})
    for t in trials:  # worker trial spans parent under head dispatch spans
        assert t["args"]["parent_id"] in dispatch
    # Head-node aggregation: the workers' registry snapshots summed.
    state = json.load(open(os.path.join(root, "experiment_state.json")))
    cluster = state["obs"]["cluster"]
    assert state["obs"]["cluster_workers"] == 1
    assert any(k.startswith("checkpoint/") for k in cluster), cluster
    assert cluster.get("obs/spans_recorded", 0) > 0


# ---------------------------------------------------------------------------
# chaos: a telemetry failure must never fail the run (satellite)
# ---------------------------------------------------------------------------


def _quadratic(config):
    for _ in range(3):
        tune.report(loss=(config["x"] - 2.0) ** 2, checkpoint={"x": [1.0]})


def test_trace_export_faults_absorbed_same_best_trial(tmp_results):
    space = {"x": tune.uniform(0.0, 6.0)}
    control = tune.run(
        _quadratic, space, metric="loss", mode="min", num_samples=4,
        seed=11, storage_path=tmp_results, name="obs_chaos_control",
        verbose=0, trace=True,
    )
    with chaos.active(chaos.FaultPlan(seed=3, trace_export_error_rate=1.0)):
        faulted = tune.run(
            _quadratic, space, metric="loss", mode="min", num_samples=4,
            seed=11, storage_path=tmp_results, name="obs_chaos_faulted",
            verbose=0, trace=True,
        )
        fired = chaos.active_plan().snapshot()
    assert faulted.best_config == control.best_config
    assert fired.get("trace_export_errors", 0) >= 1
    state = json.load(open(os.path.join(
        tmp_results, "obs_chaos_faulted", "experiment_state.json"
    )))
    # every export failed (rate 1.0): counted, run unaffected, no merge
    assert state["obs"]["export_failures"] >= 1
    assert "trace" not in state["obs"]


# ---------------------------------------------------------------------------
# CLI: export / merge / summarize (satellite)
# ---------------------------------------------------------------------------


def test_trace_cli_export_and_summarize(tmp_results, capsys):
    from distributed_machine_learning_tpu.__main__ import main

    tune.run(
        _real_epoch_trainable, {"lr": tune.uniform(1e-4, 1e-2)},
        metric="loss", mode="min", num_samples=2,
        storage_path=tmp_results, name="obs_cli", verbose=0, trace=True,
    )
    root = os.path.join(tmp_results, "obs_cli")
    main(["trace", "export", root])
    out_path = capsys.readouterr().out.strip()
    assert out_path.endswith("trace.json") and os.path.exists(out_path)

    state = json.load(open(os.path.join(root, "experiment_state.json")))
    trial_id = state["trials"][0]["trial_id"]
    main(["trace", "summarize", root, "--trial", trial_id, "--json"])
    doc = json.loads(capsys.readouterr().out)
    phases = {r["phase"]: r for r in doc["phases"]}
    assert {"trial.dispatch", "trial", "epoch"} <= set(phases), phases
    assert phases["epoch"]["count"] == 2
    assert phases["trial"]["total_ms"] >= phases["epoch"]["total_ms"]

    merged = os.path.join(root, "merged_again.json")
    main(["trace", "merge", root, "-o", merged])
    capsys.readouterr()
    assert json.load(open(merged))["traceEvents"]

    with pytest.raises(SystemExit) as exc:
        main(["trace", "export", os.path.join(root, "nothing_here")])
    assert exc.value.code == 1

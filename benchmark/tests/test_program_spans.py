"""The three readers of the program's spans on a synthetic trace with
known answers, and the loader on a small trace recorded here."""

import importlib
import types

import pytest

from benchmark import program_spans as P
from benchmark import trace as T


def S(name, start, dur, **attrs):
    return P.Span(name, float(start), float(dur), attrs)


def synthetic():
    """Two epochs of one trial.  The device runs 2-6 and 10-14 in a window
    of 0-20; the trial's thread lives 1-16; a writer thread saves beside
    it; the runner's thread sets up before it and tears down after it."""
    dev = T.DeviceTrace(ops=[("fusion", 2.0, 4.0), ("fusion", 10.0, 4.0)])
    trace = T.Trace(devices={"/device:TPU:0": dev})
    runner = [
        S("run.setup", 0.0, 0.5),
        S("experiment", 0.5, 16.0),
        S("runner.process_result", 8.0, 0.5, iteration=1),
        S("runner.process_result", 15.5, 0.25, iteration=2),
        S("run.teardown", 16.5, 2.5),
    ]
    trial = [
        S("trial", 1.0, 15.0),
        S("trial.setup", 1.0, 0.75),
        S("epoch", 1.75, 4.75), S("epoch.dispatch", 1.75, 0.5),
        S("epoch.readback", 2.25, 4.25),
        S("report", 7.0, 2.0, iteration=1),
        S("report.ckpt_snapshot", 7.0, 0.5, leaves=3),
        S("report.decide_wait", 7.75, 1.25),
        S("epoch", 9.5, 5.0),
        S("report", 15.0, 1.0, iteration=2),
    ]
    writer = [S("ckpt.save", 7.5, 4.0, bytes=10), S("ckpt.save", 15.5, 3.0)]
    return trace, [runner, trial, writer]


def run_with(trace, lines, window=(0.0, 20.0)):
    run = types.SimpleNamespace(trace=trace, trace_window=window,
                                work_dir="/nonexistent", facts={})
    run.facts["program_spans"] = lines
    return run


def read(reader, spec, run):
    return importlib.import_module(f"benchmark.readers.{reader}").read(spec, run)


def test_mean_of_one_name_and_cost_per_item():
    trace, lines = synthetic()
    run = run_with(trace, lines)
    assert read("program_span_mean", {"span": "epoch"}, run) == pytest.approx(4875.0)
    # the writer thread's spans are timed like any other
    assert read("program_span_mean", {"span": "ckpt.save"}, run) == pytest.approx(3500.0)
    emit = [[S("vec.emit", 0, 0.25, results=300), S("vec.emit", 1, 0.5, results=200)]]
    per = read("program_span_mean",
               {"span": "vec.emit", "per": "results", "units_per_s": 1e6},
               run_with(trace, emit))
    assert per == pytest.approx(0.75 / 500 * 1e6)
    assert read("program_span_mean", {"span": "nothing"}, run) is None


def test_idle_inside_a_span_per_span():
    trace, lines = synthetic()
    run = run_with(trace, lines)
    # report 7-9 is all idle, report 15-16 is all idle: 3 s over 2 reports
    assert read("program_span_idle", {"span": "report"}, run) == pytest.approx(1500.0)
    # epochs 1.75-6.5 and 9.5-14.5: idle 0.25 + 0.5, and 0.5 + 0.5
    assert read("program_span_idle", {"span": "epoch"}, run) == pytest.approx(875.0)


def test_idle_goes_to_the_thread_that_drives_the_device():
    trace, lines = synthetic()
    gaps = P.idlest_gaps(trace, (0.0, 20.0))
    assert gaps == [(0.0, 2.0), (6.0, 10.0), (14.0, 20.0)]
    by = P.idle_by_span(gaps, lines, "epoch", "experiment")
    # the writer's ckpt.save covers 7.5-11.5 and 15.5-18.5 and takes nothing
    assert "ckpt.save" not in by
    assert by["run.setup"] == pytest.approx(0.5)        # before the trial
    assert by["experiment"] == pytest.approx(0.5 + 0.5)  # 0.5-1 and 16-16.5
    assert by["trial.setup"] == pytest.approx(0.75)
    assert by["epoch.dispatch"] == pytest.approx(0.25)
    assert by["epoch.readback"] == pytest.approx(0.5)
    assert by["trial"] == pytest.approx(3 * 0.5)  # 6.5-7, 9-9.5, 14.5-15
    assert by["report.ckpt_snapshot"] == pytest.approx(0.5)
    assert by["report"] == pytest.approx(0.25 + 1.0)
    assert by["report.decide_wait"] == pytest.approx(1.25)
    assert by["epoch"] == pytest.approx(0.5 + 0.5)      # 9.5-10, 14-14.5
    # the runner's process_result lies under the trial's decide_wait
    assert "runner.process_result" not in by
    assert by["run.teardown"] == pytest.approx(2.5)
    assert by[""] == pytest.approx(1.0)                 # 19-20
    assert sum(by.values()) == pytest.approx(12.0)
    spec = {"launch": "epoch", "starter": "experiment",
            "roots": ["experiment", "trial", "vec.run"]}
    share = read("program_span_unattributed", spec, run_with(trace, lines))
    assert share == pytest.approx(100.0 * (1.0 + 1.5 + 1.0) / 12.0)
    # with the launching thread alone, what the runner explains is lost
    alone = P.idle_by_span(gaps, lines, "epoch")
    assert alone[""] == pytest.approx(1.0 + 4.0)         # 0-1 and 16-20


def test_a_program_without_the_bridge_reads_nothing():
    trace, _ = synthetic()
    run = run_with(trace, [])
    for reader, spec in (
        ("program_span_mean", {"span": "epoch"}),
        ("program_span_idle", {"span": "report"}),
        ("program_span_unattributed", {"launch": "epoch", "roots": []}),
    ):
        assert read(reader, spec, run) is None
    untraced = types.SimpleNamespace(trace=None, facts={})
    assert read("program_span_mean", {"span": "epoch"}, untraced) is None


def test_innermost_pieces_do_not_overlap():
    pieces = P.innermost([S("a", 0, 10), S("b", 1, 2), S("c", 1.5, 0.5), S("d", 12, 1)])
    assert pieces == [
        (0.0, 1.0, "a"), (1.0, 1.5, "b"), (1.5, 2.0, "c"), (2.0, 3.0, "b"),
        (3.0, 10.0, "a"), (12.0, 13.0, "d"),
    ]


def test_loader_keeps_the_programs_spans_line_by_line(tmp_path):
    import threading

    import jax

    from distributed_machine_learning_tpu import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            with obs.span("outer", {"n": 3}):
                worker = threading.Thread(
                    target=lambda: obs.span("beside", {"trial_id": "t"}).end()
                )
                worker.start()
                worker.join(timeout=30)
                with obs.span("inner"):
                    pass
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    window = T.annotation_window(T.load(path), "bench:window")
    lines = P.load(path, window)
    assert len(lines) == 2
    main = next(line for line in lines if any(s.name == "outer" for s in line))
    assert {s.name for s in main} == {"outer", "inner"}
    assert P.named(lines, "outer")[0].attrs["n"] == 3
    assert P.named(lines, "beside")[0].attrs["trial_id"] == "t"
    assert P.named([main], "beside") == []

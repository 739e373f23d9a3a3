#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It loads the cell (its configuration, its
traffic file and the driver that the traffic file names), sets up and
warms up (all of it ``setup_s``), measures for ``--seconds``, reads the
device's peak memory, frees the program's state, checks what the timed
path produced against the plain reference, and prints one JSON line last.
There is no CPU mode: a run that finds no TPU, or fewer chips than the
cell asks for, exits 3 and prints no result.

Everything that belongs to one cell is data that this file finds by the
names in ``BENCHMARK.json``: ``configs/<config>.json`` (the ``file`` of
the configuration), ``traffic/<traffic>.json``, ``drivers/<kind>.py``,
``metrics/<metric>.json`` and ``readers/<reader>.py``.
"""

from __future__ import annotations

import time

_PROCESS_START = time.time()

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def load(cls, root: str, name: str) -> "Cell":
        bench = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"no cell {name!r} in BENCHMARK.json (have {sorted(cells)})"
            )
        entry = cells[name]
        cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]

        def reported_here(metric: dict) -> bool:
            return name in metric.get("workloads", [name])

        return cls(
            name=name,
            chips=int(entry["chips"]),
            config_name=entry["config"],
            config=load_json(root, cfg_entry["file"]),
            traffic_name=entry["traffic"],
            traffic=load_json(HERE, "traffic", entry["traffic"] + ".json"),
            end_to_end=[m for m in bench["end_to_end"] if reported_here(m)],
            per_layer=[m for m in bench["per_layer"] if reported_here(m)],
        )


@dataclass
class Run:
    """What one run hands from stage to stage and to the metric readers."""

    cell: Cell
    seed: int
    seconds: float
    traced: bool
    devices: list
    work_dir: str
    peaks: dict
    # filled by the window
    metrics: Dict[str, float] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    spans: Dict[str, List[float]] = field(default_factory=dict)
    counters: Dict[str, float] = field(default_factory=dict)
    facts: Dict[str, Any] = field(default_factory=dict)
    window_s: float = 0.0
    # filled after the window
    memory_peak_bytes: int = 0
    memory_limit_bytes: int = 0
    trace: Any = None
    trace_window: Any = None

    def annotate(self, name: str):
        """A host span in the profiler's own trace (a no-op span when no
        trace is being taken)."""
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)


def device_record(devices) -> dict:
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak(devices):
    """Peak bytes on the fullest chip and that chip's limit.

    The allocator's ``peak_bytes_in_use`` counts the arrays a process holds
    and leaves out the scratch memory a compiled program holds while it
    runs (seen on the chip, PR 26: the trainer at batch 16, 32 and 48 read
    the same 1.8 to 2.1 GB, while batch 64 was refused for 15.4 GB of
    temporaries).  So the peak is the larger of the allocator's peak and
    of what the largest loaded program needs while it runs, as the runtime
    reports it for each program and device: its arguments, its results that
    do not alias an argument, and its temporaries."""
    peak = limit = 0
    for d in devices:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) >= peak:
            peak = int(stats.get("peak_bytes_in_use", 0))
            limit = int(stats.get("bytes_limit", 0))
    program = 0
    for executable in devices[0].client.live_executables():
        try:
            m = executable.get_compiled_memory_stats()
        except Exception as exc:  # noqa: BLE001 - a program without stats
            say(f"no memory statistics for one loaded program: {exc!r}")
            continue
        program = max(program, int(
            m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
        ))
    say(f"memory: allocator peak {peak} bytes, largest program while it "
        f"runs {program} bytes, limit {limit} bytes")
    return max(peak, program), limit


def compile_events() -> float:
    """Requests to compile a program seen by this process so far (served
    from the persistent cache or not): the program's own tracker."""
    from distributed_machine_learning_tpu import compilecache

    return float(compilecache.get_tracker().snapshot()["backend_compiles"])


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(name, value, limit, ok) for every number that has a limit in the
    traffic file; the others are read and printed, not compared (PERF.md,
    section 2 says why for each)."""
    out = []
    for name, value in numbers.items():
        if name not in limits:
            say(f"check {name}: {value!r} (not compared)")
            continue
        limit = float(limits[name])
        ok = value == value and abs(value) != float("inf") and value <= limit
        out.append((name, float(value), limit, bool(ok)))
    return out


def per_layer_metrics(run: Run) -> Dict[str, dict]:
    out = {}
    for metric in run.cell.per_layer:
        spec = load_json(HERE, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec, run)
        if value is None:
            say(f"per-layer {metric['name']}: nothing to read, left out")
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             devices: list, work_dir: str, process_start: float,
             describe_to: Optional[str] = None) -> dict:
    """Everything of a run but the look for a chip; returns the result."""
    import jax

    from benchmark import trace as trace_lib

    peaks_table = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks_table:
        if devices[0].platform == "tpu":
            raise SystemExit(f"device kind {kind!r} is not in peaks.json")
        peaks = None  # tests on the CPU: no device metric is read
    else:
        peaks = peaks_table[kind]
    run = Run(cell=cell, seed=seed, seconds=seconds, traced=traced,
              devices=devices, work_dir=work_dir, peaks=peaks)
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}"
    )
    if traced:
        # The traced run measures a shorter window: per-layer numbers only.
        run.seconds = min(seconds, float(cell.traffic.get("trace_seconds", 10)))

    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, seed {seed}, {run.seconds:g}s, "
        f"{'traced' if traced else 'untraced'}, on {device_record(devices)}")
    with run.annotate("setup"):
        state = driver.setup(run)
    setup_s = time.time() - process_start
    say(f"set-up done in {setup_s:.1f}s")

    trace_dir = os.path.join(work_dir, "trace")
    compiles_before = compile_events()
    if traced:
        jax.profiler.start_trace(trace_dir)
    try:
        with run.annotate("window"):
            driver.window(run, state)
    finally:
        if traced:
            jax.profiler.stop_trace()
    run.counters["window_compiles"] = compile_events() - compiles_before
    say(f"window closed after {run.window_s:.3f}s; compile requests inside "
        f"it: {run.counters['window_compiles']:.0f}")
    run.memory_peak_bytes, run.memory_limit_bytes = memory_peak(devices)

    driver.release(run, state)
    gc.collect()
    t_check = time.time()
    checks = driver.check(run, state)
    say(f"output check took {time.time() - t_check:.1f}s")
    correct = all(ok for _, _, _, ok in checks)

    device = device_record(devices)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
    }
    if traced:
        path = trace_lib.find_xplane(trace_dir)
        if path is None:
            raise SystemExit("the profiler wrote no trace")
        if describe_to:
            with open(describe_to, "w") as f:
                f.write(trace_lib.describe(path))
        prefix = (trace_lib.DEVICE_PLANE_PREFIX
                  if devices[0].platform == "tpu" else "/device:")
        run.trace = trace_lib.load(path, prefix)
        run.trace_window = (
            trace_lib.annotation_window(run.trace, "bench:window")
            or trace_lib.trace_window(run.trace)
        )
        busy = [
            trace_lib.busy_seconds(dev, run.trace_window)
            for dev in run.trace.devices.values()
        ]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = run.trace_window[1] - run.trace_window[0]
        result["metrics"] = per_layer_metrics(run)
        result["device"] = device
        result["breakdown"] = trace_lib.breakdown(run.trace, run.trace_window)
    else:
        run.metrics["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {
            name: {"value": float(run.metrics[name]), "unit": unit}
            for name, unit in units.items()
        }
        result["device"] = device
    result["checks"] = {
        name: {"value": value, "limit": limit} for name, value, limit, _ in checks
    }
    for name, value, limit, ok in checks:
        print(f"[bench] check {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if ok else 'NOT CORRECT'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--describe-trace", metavar="FILE",
        help="with --trace 1: also write every plane and line of the trace "
             "with its busiest event names to FILE, to look at by hand",
    )
    args = parser.parse_args(argv)

    cell = Cell.load(ROOT, args.workload)
    try:
        import distributed_machine_learning_tpu  # noqa: F401
    except ImportError as exc:
        print(f"benchmark: the program is not in this checkout: {exc}",
              file=sys.stderr)
        return 3
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"jax sees {len(devices)} x {devices[0].platform}. "
            f"There is no CPU mode.", file=sys.stderr,
        )
        return 3
    devices = list(devices[: cell.chips])

    from distributed_machine_learning_tpu import compilecache

    cache_dir = compilecache.enable_persistent_cache()
    say(f"jax {jax.__version__}; compile cache at {cache_dir} "
        f"({compilecache.cache_entry_count()} entries)")
    work_dir = tempfile.mkdtemp(prefix="dml_bench_")
    try:
        result = run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), devices=devices, work_dir=work_dir,
            process_start=_PROCESS_START, describe_to=args.describe_trace,
        )
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

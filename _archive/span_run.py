"""Run one benchmark cell with the program's spans recorded (no profiler)
and print, report by report, how long each span took.  By hand:
    python3 _archive/span_run.py <checkout dir> <cell> <seed>
"""
import json
import os
import runpy
import statistics
import sys


def main():
    side, cell, seed = sys.argv[1:4]
    os.chdir(side)
    sys.path.insert(0, os.getcwd())
    from distributed_machine_learning_tpu import obs

    tracer = obs.Tracer(buffer_limit=400_000)
    obs.install_tracer(tracer)
    sys.argv = ["benchmark/run.py", "--workload", cell, "--seed", seed,
                "--seconds", "30", "--trace", "0"]
    code = 0
    try:
        runpy.run_path("benchmark/run.py", run_name="__main__")
    except SystemExit as exc:
        code = exc.code or 0
    print("SPANS " + json.dumps(dict(
        summary(tracer), side=side, cell=cell, seed=seed, rc=code,
    )), flush=True)
    return code


def summary(tracer):
    records = tracer.records()
    names = ("epoch", "epoch.dispatch", "epoch.readback", "report",
             "report.ckpt_snapshot", "report.ckpt_drain",
             "report.decide_wait", "ckpt.save", "runner.process_result",
             "run.teardown", "run.setup")
    by_name = {n: [] for n in names}
    for r in sorted(records, key=lambda r: r["ts"]):
        if r.get("name") in by_name:
            by_name[r["name"]].append(round(r["dur"] / 1e3, 1))
    out = {"dropped": tracer.dropped()}
    for n, v in by_name.items():
        if v:
            out[n] = {"n": len(v), "median": statistics.median(v),
                      "max": max(v), "last": v[-14:]}
    return out


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read on the chip, at a cell's own size and in one process, what the
limits in its traffic file are set from.

    python3 benchmark/controls.py --workload <cell> --seeds 1,2,3,4 --planted 2 --group 2

For every seed: the numbers of a sound run of the program against the
plain reference (no measured window for training, one sweep for a sweep).
For the first ``--planted`` seeds: the reference put in the program's
place in the precision below the one the configuration states (the
traffic file's ``control``), and with each fault planted that the
reference can carry; for a training cell also the program itself with
half of every batch left out of its loss.  One JSON line a reading, every
number the cell reads, compared or not.  The benchmark's own runs never
call this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read(cell, seeds, planted: int, devices, group=1, broken=0):
    """Yield one dict for each reading of ``cell``."""
    from benchmark.reference import regressor as ref
    from benchmark.run import Run

    def make_run(seed):
        return Run(cell=cell, seed=seed, seconds=0.0, traced=False,
                   devices=devices, peaks=None,
                   work_dir=tempfile.mkdtemp(prefix="dml_controls_"))

    driver = importlib.import_module(f"benchmark.drivers.{cell.traffic['driver']}")
    control = getattr(ref, cell.traffic["control"])
    for seed, what, numbers in driver.readings(
        make_run, seeds, planted, control, group, broken
    ):
        yield {"cell": cell.name, "seed": seed, "what": what, **numbers}


def main(argv=None) -> int:
    from benchmark.run import Cell

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--planted", type=int, default=3)
    parser.add_argument(
        "--group", type=int, default=1,
        help="training: this many seeds in a row share the first one's data, "
             "so that the program traces its epoch once for them",
    )
    parser.add_argument(
        "--broken", type=int, default=0,
        help="training: on this many of the first seeds also run the program "
             "itself with half of every batch left out of its loss",
    )
    args = parser.parse_args(argv)
    import jax

    from distributed_machine_learning_tpu import compilecache

    cell = Cell.load(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("controls: no TPU", file=sys.stderr)
        return 3
    compilecache.enable_persistent_cache()
    for line in read(cell, [int(s) for s in args.seeds.split(",")],
                     args.planted, list(devices[: cell.chips]), args.group,
                     args.broken):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

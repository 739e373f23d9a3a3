"""Do the reference's draws equal the program's on the TPU?  Run through the
chip tool: ``python3 benchmark/tests/chip_equal.py``.

The tiny cells with both sides in true float32 (matmul precision
``highest``), so that any gap left is a difference in weights, batch order
or dropout masks and not rounding.  PR 26 read: the trainer (not vmapped,
hardware generator) 7e-07; the vmapped sweep under the hardware generator
1.17 and 0.36, where the CPU (threefry) reads 3e-07 (PERF.md, Open
questions 1).  Not collected by pytest: it needs the chip.
"""
import json
import pathlib
import sys
import tempfile

sys.path.insert(0, ".")
sys.path.insert(0, "benchmark/tests")
import jax

jax.config.update("jax_default_matmul_precision", "highest")
import tiny

for seed in (11, 12):
    for cell in (tiny.train_cell(), tiny.sweep_cell()):
        r = tiny.drive(cell, pathlib.Path(tempfile.mkdtemp()), seed=seed)
        print("EQUAL", cell.name, seed, json.dumps(r["checks"]))

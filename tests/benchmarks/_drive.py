"""Shared by the tests of the benchmark: one run of a tiny cell on the CPU
through the kind's own ``run_cell``, skipping only ``run.py``'s look for a
chip. The cells here are test sizes (tests/benchmarks/data), never cells of
BENCHMARK.json."""

import contextlib
import io
import json
import os
import time

from benchmarks import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(cell_name: str) -> tuple:
    cell = harness.load_json(os.path.join(DATA, f"{cell_name}.json"))
    cfg = harness.load_json(os.path.join(DATA, "tiny_config.json"))
    return cell, cfg


def run(cell_name: str, *, seed: int, seconds: float, fault=None,
        trace: bool = False) -> tuple:
    """(the result's last line as a dict, everything printed before it).
    ``fault(trainer)`` breaks the timed path underneath the run: it is
    applied to the trainer that the kind's own ``make_trainer`` builds."""
    cell, cfg = tiny(cell_name)
    bench = harness.load_benchmark()
    os.makedirs(harness.WORK, exist_ok=True)
    kind = harness.load_kind(cell["kind"])
    if fault is not None:
        sound = kind.make_trainer

        def broken(cell, cfg):
            model_cfg, trainer = sound(cell, cfg)
            fault(trainer)
            return model_cfg, trainer
        kind.make_trainer = broken
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = kind.run_cell(
            cell, cfg, seed=seed, seconds=seconds, trace=trace,
            t_start=time.perf_counter(), device=dict(CPU),
            ledger=harness.CompileLedger(), bench=bench)
        harness.finish(**result)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]

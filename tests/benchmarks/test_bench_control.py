"""The control of the train cells, kept at a size a test run can hold: the
reference put in the program's place and computed one precision below the
one the configurations state (fp8 for bfloat16) has to come out not correct
under every train cell's own limits, and so has the reference with half of
the batch left out. The reference in the stated bfloat16 passes them."""

import os

import pytest

from benchmarks import harness
from benchmarks.kinds import train

from _drive import DATA

BENCH = harness.load_benchmark()
TRAIN_CELLS = [w["name"] for w in BENCH["workloads"]
               if harness.load_cell(w["name"], BENCH)[0]["kind"] == "train"]
SEEDS = [2 ** 31 + 11, 5, 900000007]


@pytest.fixture(scope="module")
def readings():
    """The reference's first steps at the test size in each precision, per
    optimizer and seed: computed once for all the cells' limits."""
    cfg = harness.load_json(os.path.join(DATA, "mid_config.json"))
    out = {}
    for optimizer in sorted({harness.load_cell(n, BENCH)[0]["recipe"]["optimizer"]
                             for n in TRAIN_CELLS}):
        cell = {"recipe": {"optimizer": optimizer, "learning_rate": 3e-4,
                           "grad_clip_norm": 0.5},
                "traffic": {"batch": 32, "text_tokens": [8, 64]}}
        for seed in SEEDS:
            sound = train.reference_numbers(cell, cfg, seed)
            out[optimizer, seed] = {
                "bf16": train.compare(train.reference_numbers(
                    cell, cfg, seed, precision="bf16"), sound),
                "fp8": train.compare(train.reference_numbers(
                    cell, cfg, seed, precision="fp8"), sound),
                "half": train.compare(train.reference_numbers(
                    cell, cfg, seed, rows=slice(0, 16)), sound)}
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_the_control_and_the_fault_fail_the_cells_limits(readings, name, seed):
    cell, _ = harness.load_cell(name, BENCH)
    got = readings[cell["recipe"]["optimizer"], seed]
    assert harness.judge(got["bf16"], cell["limits"])[0] is True
    assert harness.judge(got["fp8"], cell["limits"])[0] is False
    assert harness.judge(got["half"], cell["limits"])[0] is False

#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to measure without a TPU (exit non-zero, no result). Builds weights
and inputs from ``--seed``, warms only that cell's shapes through the
persistent compile cache, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints the contract's one JSON
object as the last line of standard output. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse   # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmarks import harness
    bench = harness.load_benchmark()
    cell, cfg = harness.load_cell(args.workload, bench)
    device = harness.require_tpu(cell["chips"])
    from benchmarks import arith
    arith.peaks_for(device["kind"])           # an unknown kind is an error
    ledger = harness.CompileLedger()
    harness.enable_compile_cache()
    os.makedirs(harness.WORK, exist_ok=True)
    kind = harness.load_kind(cell["kind"])
    result = kind.run_cell(cell, cfg, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           device=device, ledger=ledger, bench=bench)
    harness.finish(**result)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Where a cell's limits come from: the readings that "correct" is set
between, taken on the chip at the cell's own size, in one process.

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 \\
        --control-seeds 3 --first-seed 7000 --out chiprun_out/calibrate

For every number the cell compares it prints, and writes as JSON, the lower
readings (sound runs of the program against the reference, one per seed),
the control's readings (the reference put in the program's place, computed
one precision below the one the configuration states) and each fault's
readings. A benchmark run never calls this; the limits in the cell's file
are set from its output by hand and recorded in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def summarize(readings: dict) -> dict:
    """{number: {"lower": max over the program's seeds, "upper": the least of
    the control's and the qualifying faults' readings, ...}}."""
    out = {}
    for name in readings["program"][0]["compared"]:
        lower = [r["compared"][name] for r in readings["program"]]
        row = {"program": lower, "lower": max(lower)}
        others = {k: v for k, v in readings.items()
                  if k not in ("program", "faults") and isinstance(v, list)}
        others.update(readings.get("faults", {}))
        for kind, rows in others.items():
            row[kind] = [r["compared"][name] for r in rows]
        out[name] = row
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "calibrate"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at a tiny size; its readings set nothing")
    args = ap.parse_args()

    from benchmarks import harness
    bench = harness.load_benchmark()
    cell, cfg = harness.load_cell(args.workload, bench)
    device = (harness.describe_device(cell["chips"]) if args.allow_cpu
              else harness.require_tpu(cell["chips"]))
    harness.enable_compile_cache()
    os.makedirs(harness.WORK, exist_ok=True)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings = harness.load_kind(cell["kind"]).calibrate(
        cell, cfg, seeds=seeds, control_seeds=seeds[:args.control_seeds])
    readings.update(workload=cell["name"], device=device, seeds=seeds,
                    summary=summarize(readings))
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{cell['name']}.json")
    with open(path, "w") as f:
        json.dump(readings, f, indent=1)
    for name, row in readings["summary"].items():
        print(f"{name}: lower {row['lower']:.6g} over {len(row['program'])} "
              f"seeds; " + "; ".join(
                  f"{k} {min(v):.6g}..{max(v):.6g}" for k, v in row.items()
                  if k not in ("program", "lower")), flush=True)
    print(f"written {path}", flush=True)


if __name__ == "__main__":
    main()

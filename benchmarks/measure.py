#!/usr/bin/env python3
"""The runs a cell's bounds are set from, as the contract asks for them: sets
of runs of one cell, each run a new process of ``run.py`` with another
``--seed``, the same seeds in every set, then per metric each set's median
and spread (``arith.spread``). This process never touches jax, so each run
has the chip to itself.

    python3 benchmarks/measure.py --workload <name> --sets 2 --runs 6 \\
        --first-seed 3400000000 [--trace 1] [--tag <directory name>]

Every run's output is kept under ``chiprun_out/<tag>/``; PERF.md's readings
name the tag they come from.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=3_400_000_000)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()

    from benchmarks import arith, harness
    bench = harness.load_benchmark()
    seconds = args.seconds or bench["run_seconds"]
    out_dir = os.path.join(REPO, "chiprun_out", args.tag or args.workload)
    os.makedirs(out_dir, exist_ok=True)
    seeds = [args.first_seed + 104729 * i for i in range(args.runs)]
    sets = []
    for s in range(args.sets):
        lines = []
        for seed in seeds:
            cmd = [*bench["command"], "--workload", args.workload, "--seed",
                   str(seed), "--seconds", str(seconds), "--trace",
                   str(args.trace)]
            done = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
            with open(os.path.join(out_dir, f"set{s}_seed{seed}_trace"
                                            f"{args.trace}.log"), "w") as f:
                f.write(done.stdout + "\n--- stderr ---\n" + done.stderr[-8000:])
            last = done.stdout.strip().splitlines()[-1] if done.stdout.strip() else ""
            if done.returncode != 0 or not last.startswith("{"):
                print(f"set {s} seed {seed}: rc {done.returncode}\n"
                      f"{done.stderr[-2000:]}", flush=True)
                continue
            line = json.loads(last)
            lines.append(line)
            print(f"set {s} seed {seed}: correct {line['correct']} "
                  + " ".join(f"{k} {v['value']:.6g}"
                             for k, v in line["metrics"].items())
                  + " | " + " ".join(f"{k} {v[0]:.4g}/{v[1]:.4g}"
                                     for k, v in line["compared"].items())
                  + f" | peak {line['device']['memory_peak_bytes'] / 2**30:.2f} GiB"
                  + (f" busy {line['device']['busy_s']:.3f}/"
                     f"{line['device']['window_s']:.3f} s"
                     if "busy_s" in line["device"] else ""), flush=True)
        sets.append(lines)
    for name in sorted({k for lines in sets for l in lines for k in l["metrics"]}):
        for s, lines in enumerate(sets):
            values = [l["metrics"][name]["value"] for l in lines
                      if name in l["metrics"]]
            if len(values) >= 2:
                print(f"{name} set {s}: median {statistics.median(values):.6g}"
                      f", spread {100 * arith.spread(values):.3f} % over "
                      f"{len(values)} runs ({min(values):.6g}.."
                      f"{max(values):.6g})", flush=True)


if __name__ == "__main__":
    main()

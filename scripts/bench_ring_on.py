#!/usr/bin/env python3
"""One run of a benchmark cell with grafttrace's span ring on, to measure what
tracing costs when it is on (PERF.md, CHANGES.md): the arguments are
``benchmarks/run.py``'s, the result line is its own.

    python3 scripts/bench_ring_on.py --workload <name> --seed <n> --seconds <s> --trace 0

The benchmark itself runs with the ring off (``ObsConfig.trace`` False, the
default); this turns it on for the whole process before the run starts, so
every span of ``fit()`` also lands in the ring (no export at the end)."""

import os
import runpy
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dalle_tpu import obs  # noqa: E402

obs.configure()
sys.argv[0] = os.path.join(REPO, "benchmarks", "run.py")
runpy.run_path(sys.argv[0], run_name="__main__")

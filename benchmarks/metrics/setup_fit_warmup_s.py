"""Seconds of set-up from ``fit()``'s entry to the end of its first step's
sync: the program's ``fit/warmup`` span (rollback snapshot, first batch, the
step program's compile or load from the cache, the first step on the device)."""

UNIT = "s"

from benchmarks import program_names


def read(run):
    return program_names.phase_total_s("fit/warmup")

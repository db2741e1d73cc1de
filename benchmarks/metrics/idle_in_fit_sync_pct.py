"""Share of the traced window in which the device idled while the host was in
``fit/sync`` (the fetch of a step's metrics)."""

UNIT = "%"

from benchmarks import program_names


def read(run):
    return program_names.idle_share_pct(run, "fit/sync")

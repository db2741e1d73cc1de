"""Share of the traced window in which the device idled while the host was in
``fit/dispatch``: the program's span on the profiler's clock, over the gaps
between the device's operations."""

UNIT = "%"

from benchmarks import program_names


def read(run):
    return program_names.idle_share_pct(run, "fit/dispatch")

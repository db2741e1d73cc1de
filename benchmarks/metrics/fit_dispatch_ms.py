"""Host time of one dispatch: the median over the window's records of
``t_dispatch_s``, the duration of ``fit()``'s ``fit/dispatch`` span (the chaos
hook, the batch's placement and the jitted call's return). With a sync every
step the device waits through all of it."""

UNIT = "ms"

from benchmarks import program_names


def read(run):
    return program_names.record_median_ms(run, "t_dispatch_s")

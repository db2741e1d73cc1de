"""Host time between a step's sync and the next batch: the median over the
window's records of ``t_after_s``, the duration of ``fit()``'s ``fit/after_step``
span of the iteration before the record's (watchdog beat, ``on_step``, the
writer, the save decision)."""

UNIT = "ms"

from benchmarks import program_names


def read(run):
    return program_names.record_median_ms(run, "t_after_s")

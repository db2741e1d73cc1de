"""Seconds of set-up inside the trainer's construction: the total of the
program's ``trainer/init`` span (eager model init, optimizer state, placement
on the mesh, the step's jit wrapper) in the run's process."""

UNIT = "s"

from benchmarks import program_names


def read(run):
    return program_names.phase_total_s("trainer/init")

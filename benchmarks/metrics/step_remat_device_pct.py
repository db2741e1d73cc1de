"""The share of the traced busy time spent recomputing, in every layer:
operations whose path holds ``rematted_computation`` (work the forward pass
had done once and ``nn.remat`` runs again for the backward pass). By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "phases", "remat")

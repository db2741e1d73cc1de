"""The share of the traced busy time that the split by scope could not place:
operations whose path holds no scope of the program, and events the
program's table does not name. The other shares' error bar. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "unscoped")

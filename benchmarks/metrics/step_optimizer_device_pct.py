"""The share of the traced busy time under the scopes ``optimizer`` and
``clip``: every leaf's update, the gradients' global norm and the clip. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "optimizer")

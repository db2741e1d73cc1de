"""The share of the traced busy time under the scope ``ff``: the dense MLPs
(GEGLU, or SwiGLU in a dense layer of a routed stack; a shared expert counts
with its routed layer). By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "ff")

"""The share of the traced busy time under the scopes ``moe/router``,
``moe/dispatch``, ``moe/experts``, ``moe/combine`` and ``moe/shared``: the
whole routed layer, the grouped-product kernels and the shared expert
included. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "moe")

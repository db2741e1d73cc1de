"""The share of the traced busy time under the scopes ``loss`` and
``loss/mtp``: the vocabulary head and its cross-entropy, forward, recompute
and backward, of every head pass. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "loss")

"""The share of the traced busy time under the scope ``attn_core``: scores,
softmax and weighted values on whichever tier runs them (dense, the fused or
the flash kernels), with what surrounds the kernels' calls there (the
repeat of grouped keys and values, pads), forward, recompute and backward. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "attn_core")

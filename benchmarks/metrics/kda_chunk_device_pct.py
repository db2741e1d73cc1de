"""The share of the traced busy time under the scope ``attn/kda_chunk``: the
linear-attention layers' work within chunks (``ops/kda.py``), forward,
recompute and backward; the recurrence across chunks is apart
(``kda_state_device_pct``). By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "kda_chunk")

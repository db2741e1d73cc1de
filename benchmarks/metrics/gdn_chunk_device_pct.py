"""The share of the traced busy time under the scope ``attn/gdn_chunk``: the
Gated DeltaNet layers' work within chunks (``ops/kda.py`` with one decay a
head), forward, recompute and backward; the recurrence across chunks is
apart (``gdn_state_device_pct``). By the program's own scope table
(``benchmarks/scope_time.py``): None without it, and None where no
operation ran under the scope (a program without the form)."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    split = scope_time.by_layer(run)
    if split is None or "gdn_chunk" not in split["layers"]:
        return None
    return scope_time.share_pct(run, "layers", "gdn_chunk")

"""The share of the traced busy time under the scope ``attn/gdn_state``: the
Gated DeltaNet layers' steps of the state across chunks and the read-out's
head norm (``ops/kda.py`` with one decay a head), forward, recompute and
backward. By the program's own scope table (``benchmarks/scope_time.py``):
None without it, and None where no operation ran under the scope."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    split = scope_time.by_layer(run)
    if split is None or "gdn_state" not in split["layers"]:
        return None
    return scope_time.share_pct(run, "layers", "gdn_state")

"""The share of the traced busy time in the attention layers' projections,
gates, convolutions and head norms (the scopes ``attn/qkv``, ``attn/out``,
``attn/gate``, ``attn/mla_*``, ``attn/gqa_qkv``, ``attn/kda_proj``,
``attn/kda_conv``, ``attn/kda_gates``): all of an attention layer but its
core. By the program's own scope table
(``benchmarks/scope_time.py``): None without it."""

UNIT = "%"

from benchmarks import scope_time


def read(run):
    return scope_time.share_pct(run, "layers", "attn_proj")

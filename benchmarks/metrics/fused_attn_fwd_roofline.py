"""The fused attention forward kernel's share of its roofline, by the name the
program gives it (``fused_attn_fwd``). A forward recomputed in the backward
pass adds to the time and not to the work."""

UNIT = "%"

from benchmarks import program_names


def read(run):
    return program_names.fused_attention_roofline_pct(run, "fused_attn_fwd", False)

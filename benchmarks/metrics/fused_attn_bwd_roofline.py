"""The fused attention backward kernel's share of its roofline, by the name
the program gives it (``fused_attn_bwd``)."""

UNIT = "%"

from benchmarks import program_names


def read(run):
    return program_names.fused_attention_roofline_pct(run, "fused_attn_bwd", True)

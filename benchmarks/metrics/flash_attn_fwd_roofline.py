"""The flash attention forward kernel's share of its roofline, by the name
the program gives it (``flash_attn_fwd``): 64 query heads against 8 key and
value heads' bytes, one call a softmax layer a step. A forward recomputed in
the backward pass adds to the time and not to the work."""

UNIT = "%"

from benchmarks import flash_roofline


def read(run):
    return flash_roofline.roofline_pct(run, ("flash_attn_fwd",),
                                       backward=False)

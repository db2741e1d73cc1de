"""The grouped product's forward kernel (``moe_gmm_fwd``) against its
roofline: the least time for the counted rows and the held weights, one
forward an expert layer a step (``moe_roofline.roofline_pct``), over the
kernel's device time. The forward that the backward pass recomputes adds to
the time and not to the work."""

UNIT = "%"

from benchmarks import moe_roofline


def read(run):
    return moe_roofline.roofline_pct(run, ("moe_gmm_fwd",), backward=False)

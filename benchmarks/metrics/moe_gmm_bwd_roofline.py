"""The grouped product's backward kernels (``moe_gmm_dlhs`` for the rows'
gradient, ``moe_gmm_drhs`` for the weights') against their roofline: the
least time for both gradients of the counted rows
(``moe_roofline.roofline_pct``) over the two kernels' device time."""

UNIT = "%"

from benchmarks import moe_roofline


def read(run):
    return moe_roofline.roofline_pct(run, ("moe_gmm_dlhs", "moe_gmm_drhs"),
                                     backward=True)

"""The grouped product's three kernels' share of the device's busy time in
the traced part of the window."""

UNIT = "%"

from benchmarks import moe_roofline


def read(run):
    seconds = moe_roofline.kernel_seconds(run, moe_roofline.KERNELS)
    if seconds is None:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]

"""The fused Pallas attention kernels' share of their roofline: the least
time the chip could take for the traced steps' attention calls (the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, both from shapes:
``arith.causal_attention_cost``) over the summed device time of the Mosaic
attention events in the trace. Silent where the trace holds no such event
(a configuration that stays dense)."""

UNIT = "%"

from benchmarks import arith, xplane


def kernel_seconds(ops: dict, batch: int, n: int, inner: int) -> float:
    """The program gives its kernels no name of their own (the ``tracing``
    issue's), so the fused attention kernels are found as the trace's Mosaic
    calls that take or give the merged (batch, n, 3 x heads x dim_head)
    tensor of queries, keys and values, which is their boundary, forward and
    backward: another Pallas kernel in the step does not fold in."""
    qkv = f"[{batch},{n},{3 * inner}]"
    return sum(s for name, s in ops.items()
               if xplane.is_mosaic(name) and qkv in name)


def read(run):
    if not run["trace"] or not run["traced"]["steps"]:
        return None
    m = run["config"]["model"]
    n = m["text_seq_len"] + m["image_fmap_size"] ** 2
    batch = run["cell"]["traffic"]["batch"]
    seconds = kernel_seconds(run["trace"]["ops"], batch, n,
                             m["heads"] * m["dim_head"])
    if seconds <= 0:
        return None
    peaks = arith.peaks_for(run["device"]["kind"])
    least = 0.0
    for backward in (False, True):
        cost = arith.causal_attention_cost(batch, m["heads"], n,
                                           m["dim_head"], backward=backward)
        least += arith.least_seconds(cost, peaks)[0]
    least *= m["depth"] * run["traced"]["steps"]
    return 100.0 * least / seconds

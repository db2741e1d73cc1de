"""What the grouped product's per-layer metrics share: the device time of
its kernels by the names the program gives them
(``program_names.kernel_seconds``) and the least time the chip could take
for the rows the traced steps counted (``arith_moe.grouped_product_cost``).
On a program without the kernels every reader finds nothing and returns
None, never 0."""

from __future__ import annotations

from benchmarks import arith, arith_moe, program_names

KERNELS = ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs")


def kernel_seconds(run: dict, kernels):
    """Summed device time of the named kernels in the traced part, or None
    without a trace or where none of them ran."""
    if not run.get("trace") or not run["traced"]["steps"]:
        return None
    seconds = sum(program_names.kernel_seconds(run["trace"]["ops"], k)
                  for k in kernels)
    return seconds if seconds > 0 else None


def traced_rows(run: dict):
    """``moe_rows_held`` summed over the traced steps' records (each record
    sums the expert layers), or None where the records do not carry it."""
    first = run["traced"]["from_step"]
    rows = [m["moe_rows_held"] for step, _, m in run["records"]
            if "moe_rows_held" in m and step > first]
    return sum(rows) if rows else None


def roofline_pct(run: dict, kernels, *, backward: bool):
    seconds = kernel_seconds(run, kernels)
    if seconds is None:
        return None
    rows = traced_rows(run)
    if not rows:
        return None
    model = run["config"]["model"]
    layers = model["depth"] - model["block"]["first_dense_layers"]
    steps = run["traced"]["steps"]
    # the cost is linear in the rows but for the weights, which every expert
    # layer of every step reads once: cost(mean rows a layer) x layers x steps
    cost = arith_moe.grouped_product_cost(model, rows / (layers * steps),
                                          backward=backward)
    least = arith.least_seconds(cost, arith.peaks_for(run["device"]["kind"]))[0]
    return 100.0 * least * layers * steps / seconds

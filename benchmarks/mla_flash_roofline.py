"""What the flash attention kernels' two per-layer metrics share in a stack
with latent attention (two head widths): the device time of the kernels by
the names the program gives them (``program_names.kernel_seconds``) and the
least time the chip could take for one call a latent layer a step
(``arith_ling3.flash_attention_cost``; a forward that the backward pass
recomputes adds time, not work). On a program without the kernels, or a
stack without a latent layer on the flash tier, the readers find nothing and
return None, never 0."""

from __future__ import annotations

from benchmarks import arith, arith_ling3, program_names


def roofline_pct(run: dict, kernels, *, backward: bool):
    if not run.get("trace") or not run["traced"]["steps"]:
        return None
    model = run["config"]["model"]
    if "mla" not in model.get("block", {}).get("attention_layers", ()):
        return None
    seconds = sum(program_names.kernel_seconds(run["trace"]["ops"], k)
                  for k in kernels)
    if seconds <= 0:
        return None
    cost = arith_ling3.flash_attention_cost(
        model, run["cell"]["traffic"]["batch"], backward=backward)
    least = arith.least_seconds(cost, arith.peaks_for(run["device"]["kind"]))[0]
    calls = arith_ling3.latent_layers(model) * run["traced"]["steps"]
    return 100.0 * least * calls / seconds

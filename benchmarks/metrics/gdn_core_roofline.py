"""The Gated DeltaNet core's share of its roofline: the least time the chip
could take for its forward and backward work, one call a linear layer a
step (``arith_olmo_hybrid.gdn_core_cost``: max of operations over the bf16
peak and bytes over the bandwidth), over the device time of the scopes
``attn/gdn_chunk`` and ``attn/gdn_state`` (``benchmarks/scope_time.py``) in
the traced steps. A forward that the backward pass recomputes adds to the
time and not to the work. None without the program's scope table or where
neither scope ran."""

UNIT = "%"

from benchmarks import arith, arith_olmo_hybrid, scope_time


def read(run):
    split = scope_time.by_layer(run)
    if split is None or not run["traced"]["steps"]:
        return None
    seconds = sum(split["layers"].get(k, 0.0)
                  for k in ("gdn_chunk", "gdn_state"))
    if seconds <= 0:
        return None
    model = run["config"]["model"]
    cost = arith_olmo_hybrid.gdn_core_cost(model,
                                           run["cell"]["traffic"]["batch"])
    least = arith.least_seconds(cost, arith.peaks_for(run["device"]["kind"]))[0]
    calls = arith_olmo_hybrid.linear_layers(model) * run["traced"]["steps"]
    return 100.0 * least * calls / seconds

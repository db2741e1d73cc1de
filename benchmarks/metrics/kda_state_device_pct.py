"""The share of the traced busy time that the device spends inside the
recurrences across chunks of the linear-attention layers (forward, the
remat's recompute and the backward pass): by the kernels' names where the
program runs them as Pallas kernels (``kda_state_fwd`` / ``kda_state_bwd``),
else by the extent of the ``while`` instructions whose carried tuple holds
the state's shape, f32[batch, heads, d_k, d_v] (the trace names a device
operation by its whole HLO instruction, result shape first). None where it
finds neither, never 0."""

UNIT = "%"

import re

from benchmarks import program_names, xplane

KERNELS = ("kda_state_fwd", "kda_state_bwd")
WHILE = re.compile(r"=\s*\(.*\)\s*while\(")


def read(run):
    trace = run.get("trace")
    block = run["config"]["model"].get("block", {})
    if not trace or not trace.get("busy_s") or "linear_num_heads" not in block:
        return None
    seconds = sum(program_names.kernel_seconds(trace["ops"], k)
                  for k in KERNELS)
    if seconds <= 0:
        state = "f32[%d,%d,%d,%d]" % (
            run["cell"]["traffic"]["batch"], block["linear_num_heads"],
            block["linear_head_dim"], block["linear_head_dim"])
        loops = [e for e in trace.get("events", ())
                 if state in e[0] and WHILE.search(e[0])]
        seconds = xplane.busy_ns(loops) / 1e9
    return 100.0 * seconds / trace["busy_s"] if seconds > 0 else None

"""The whole step's share of the chip's bf16 peak for a stack with routed
experts: FLOPs a token from the parameters a token's products touch on this
chip (``arith_moe``: the routed experts by the step's counted
``moe_rows_held``, median over the window's records) x tokens/s/chip over
the benchmark's table of peaks. In a traced run the rate is that of the part
of the window before the profiler started. Recomputed operations do not
count."""

UNIT = "%"

import statistics

from benchmarks import arith, arith_moe


def read(run):
    held = [m["moe_rows_held"] for _, _, m in run["records"]
            if "moe_rows_held" in m]
    if not held:
        return None
    model = run["config"]["model"]
    tokens_a_step = (run["cell"]["traffic"]["batch"]
                     * (model["text_seq_len"] + model["image_fmap_size"] ** 2))
    traced = run["traced"]
    rate = (traced["untraced_tokens_per_s_per_chip"] if traced
            else run["window"]["tokens_per_s_per_chip"])
    flops = arith_moe.train_flops_per_token(
        model, statistics.median(held) / tokens_a_step)
    return arith.mfu_pct(flops, rate,
                         arith.peaks_for(run["device"]["kind"])["bf16_flops"])

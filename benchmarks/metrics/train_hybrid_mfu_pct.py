"""The whole step's share of the chip's bf16 peak for a stack of linear and
softmax attention layers with routed experts: FLOPs a token from
``arith_hybrid.train_flops_per_token`` (6 x the parameters a token's
products touch on this chip, the routed experts by the records' counted
``moe_rows_held``, median, the head by its segment's own columns; plus
causal softmax attention's and the chunked delta rule's own products) x
tokens/s/chip over the benchmark's table of peaks. In a traced run the rate
is that of the part of the window before the profiler started. Recomputed
operations do not count."""

UNIT = "%"

import statistics

from benchmarks import arith, arith_hybrid


def read(run):
    model = run["config"]["model"]
    held = [m["moe_rows_held"] for _, _, m in run["records"]
            if "moe_rows_held" in m]
    if not held or "attention_layers" not in model.get("block", {}):
        return None
    tokens_a_step = (run["cell"]["traffic"]["batch"]
                     * (model["text_seq_len"] + model["image_fmap_size"] ** 2))
    traced = run["traced"]
    rate = (traced["untraced_tokens_per_s_per_chip"] if traced
            else run["window"]["tokens_per_s_per_chip"])
    flops = arith_hybrid.train_flops_per_token(
        model, statistics.median(held) / tokens_a_step)
    return arith.mfu_pct(flops, rate,
                         arith.peaks_for(run["device"]["kind"])["bf16_flops"])

"""The whole step's share of the chip's bf16 peak for a stack of linear and
latent attention layers with a dense first layer, routed experts and a
multi-token-prediction block: FLOPs a token from
``arith_ling3.train_flops_per_token`` (6 x the parameters a token's products
touch on this chip, per layer kind, router, shared expert, the routed
experts by the records' counted ``moe_rows_held``, median, the block's merge,
each head pass by its segment's own columns; plus causal softmax attention's
products at 192 / 128 and the chunked delta rule's) x tokens/s/chip over the
benchmark's table of peaks. In a traced run the rate is that of the part of
the window before the profiler started. Recomputed operations do not
count."""

UNIT = "%"

import statistics

from benchmarks import arith, arith_ling3


def read(run):
    model = run["config"]["model"]
    held = [m["moe_rows_held"] for _, _, m in run["records"]
            if "moe_rows_held" in m]
    if not held or "mtp_depth" not in model:
        return None
    tokens_a_step = (run["cell"]["traffic"]["batch"]
                     * (model["text_seq_len"] + model["image_fmap_size"] ** 2))
    traced = run["traced"]
    rate = (traced["untraced_tokens_per_s_per_chip"] if traced
            else run["window"]["tokens_per_s_per_chip"])
    flops = arith_ling3.train_flops_per_token(
        model, statistics.median(held) / tokens_a_step)
    return arith.mfu_pct(flops, rate,
                         arith.peaks_for(run["device"]["kind"])["bf16_flops"])

"""The whole step's share of the chip's bf16 peak for a dense stack of Gated
DeltaNet and softmax attention layers: FLOPs a token from
``arith_olmo_hybrid.train_flops_per_token`` (6 x the parameters a token's
products touch, the head by its segment's own columns; plus causal softmax
attention's and the chunked delta rule's own products) x tokens/s/chip over
the benchmark's table of peaks. In a traced run the rate is that of the part
of the window before the profiler started. Recomputed operations do not
count. None for a stack without ``gdn`` layers."""

UNIT = "%"

from benchmarks import arith, arith_olmo_hybrid


def read(run):
    model = run["config"]["model"]
    if "gdn" not in model.get("block", {}).get("attention_layers", ()):
        return None
    traced = run["traced"]
    rate = (traced["untraced_tokens_per_s_per_chip"] if traced
            else run["window"]["tokens_per_s_per_chip"])
    return arith.mfu_pct(arith_olmo_hybrid.train_flops_per_token(model), rate,
                         arith.peaks_for(run["device"]["kind"])["bf16_flops"])

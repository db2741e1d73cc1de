"""The whole step's share of the chip's bf16 peak: PaLM-convention FLOPs per
token from the configuration's shapes x tokens/s/chip over the benchmark's
own table of peaks. In a traced run the rate is that of the part of the
window before the profiler started. Recomputed operations do not count."""

UNIT = "%"

from benchmarks import arith


def read(run):
    traced = run["traced"]
    rate = (traced["untraced_tokens_per_s_per_chip"] if traced
            else run["window"]["tokens_per_s_per_chip"])
    peak = arith.peaks_for(run["device"]["kind"])["bf16_flops"]
    return arith.mfu_pct(arith.train_flops_per_token(run["config"]["model"]),
                         rate, peak)

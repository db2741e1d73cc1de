"""The benchmark's own arithmetic: the table of peaks, percentiles, rates,
model FLOPs and the least time a kernel could take. Later PRs cannot change
these files, so every PR computes the same number in the same way."""

from __future__ import annotations

import math

# One chip's published peaks, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
# HBM at 819 GB/s. A kind that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table of "
            f"peaks ({sorted(PEAKS)}): add a row with its source, never a "
            f"default")
    return PEAKS[device_kind]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]: the smallest value with at
    least q% of the sample at or below it. Raises on an empty sample."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(work: float, seconds: float) -> float:
    """All the work over all the time."""
    if seconds <= 0:
        raise ValueError(f"a rate over {seconds} s")
    return work / seconds


def dalle_param_count(model: dict) -> int:
    """Parameters of the DALL-E transformer as the configuration file sizes
    it (embeddings, depth x (attention + GEGLU + norms + LayerScale), final
    norm, vocabulary head)."""
    d, h, dh = model["dim"], model["heads"], model["dim_head"]
    ff = d * model.get("ff_mult", 4)
    text_vocab = model["num_text_tokens"] + model["text_seq_len"]
    vocab = text_vocab + model["image_vocab_size"]
    layer = (d * 3 * h * dh + h * dh * d + d          # qkv, out (+bias)
             + d * 2 * ff + 2 * ff + ff * d + d       # GEGLU in, out
             + 4 * d + 2 * d)                         # two norms, two scales
    return (vocab * d                                 # the two input tables
            + model["depth"] * layer + 2 * d + d * vocab + vocab)


def train_flops_per_token(model: dict) -> float:
    """PaLM's convention (Chowdhery et al. 2022, appendix B): 6 N for the
    matrix products forward and backward plus 12 L h d_head n for attention.
    Recomputed operations do not count."""
    n = model["text_seq_len"] + model["image_fmap_size"] ** 2
    return (6.0 * dalle_param_count(model)
            + 12.0 * model["depth"] * model["heads"] * model["dim_head"] * n)


def mfu_pct(flops_per_token: float, tokens_per_s_per_chip: float,
            peak_flops: float) -> float:
    return 100.0 * flops_per_token * tokens_per_s_per_chip / peak_flops


def causal_attention_cost(batch: int, heads: int, n: int, dim_head: int,
                          *, backward: bool, bytes_per_el: int = 2) -> dict:
    """Operations and bytes full causal attention needs for one call, from
    its shapes. Forward: QK^T and PV over the causal half (2 products x 2
    n^2/2 d flops per head); backward: five such products (recomputed scores,
    dV, dP, dQ, dK). Bytes: q, k, v read and the output written once
    (backward: those and their gradients, plus the output's gradient)."""
    half = n * (n + 1) / 2.0
    products = 5 if backward else 2
    flops = products * 2.0 * half * dim_head * batch * heads
    tensors = 8 if backward else 4
    nbytes = tensors * batch * heads * n * dim_head * bytes_per_el
    return {"flops": flops, "bytes": float(nbytes)}


def least_seconds(cost: dict, peaks: dict) -> tuple:
    """(the least time the chip could take, which bound it is)."""
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "bandwidth"))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the contract's."""
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

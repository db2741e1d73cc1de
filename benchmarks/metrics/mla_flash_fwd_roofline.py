"""The flash attention forward kernel's share of its roofline under latent
attention, by the name the program gives it (``flash_attn_fwd``): 32 heads,
q k^T at the query/key width of 192 and p v at the value width of 128 over
the causal half, one call a latent layer a step (the stack's and the
multi-token-prediction block's). A forward recomputed in the backward pass
adds to the time and not to the work."""

UNIT = "%"

from benchmarks import mla_flash_roofline


def read(run):
    return mla_flash_roofline.roofline_pct(run, ("flash_attn_fwd",),
                                           backward=False)

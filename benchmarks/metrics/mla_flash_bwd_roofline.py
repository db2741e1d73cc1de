"""The flash attention backward kernels' share of their roofline under
latent attention, by the names the program gives them (``flash_attn_dq`` +
``flash_attn_dkv``): five causal products, three at the query/key width of
192 and two at the value width of 128, the bytes of q, k and their gradients
at 192 and of v, the output and theirs at 128."""

UNIT = "%"

from benchmarks import mla_flash_roofline


def read(run):
    return mla_flash_roofline.roofline_pct(
        run, ("flash_attn_dq", "flash_attn_dkv"), backward=True)

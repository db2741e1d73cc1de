"""The flash attention backward kernels' share of their roofline, by the
names the program gives them (``flash_attn_dq`` + ``flash_attn_dkv``): five
causal products over 64 query heads, the bytes of queries, output and their
gradients over 64 heads and of keys, values and theirs over 8."""

UNIT = "%"

from benchmarks import flash_roofline


def read(run):
    return flash_roofline.roofline_pct(
        run, ("flash_attn_dq", "flash_attn_dkv"), backward=True)

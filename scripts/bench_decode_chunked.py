#!/usr/bin/env python
"""Long-cache decode-kernel tiers (VERDICT r4 #5): single-block vs chunked
vs dense XLA, us/layer-step at long cache lengths.

Two regimes:
  * S=1280, h8 d64 (small-model fmap-32 cache): the single-block kernel
    still fits its VMEM budget — measures whether tail-skipping ever beats
    one big DMA at 10+ blocks (the r4 S=512/4-block measurement said no).
  * S=2560, h14 d128 (flagship-head long cache): the merged block is 17.9MB
    — single-block cannot run; the chunked kernel is the only kernel tier
    and competes with dense XLA.

Timed via the dispatched-scan harness (k=64; grads off) at several lengths
(= tail-skip occupancies). Run on TPU; numbers → NEXT.md.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from _bench_util import timed_scan


def run(b, h, S, d, dtype, lengths, blks=(256, 512)):
    from dalle_tpu.ops.attention import KVCache, cached_attend
    from dalle_tpu.ops.decode_attention import (
        decode_attend_kernel, decode_attend_kernel_chunked,
        decode_kernel_supported)

    rng = np.random.RandomState(0)
    c = KVCache.init(b, h, S, d, dtype)
    k = jnp.asarray(rng.standard_normal((b, h, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, S, d)), jnp.float32)
    cache = c.append(k, v, 0)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.bfloat16)
    single_ok = decode_kernel_supported(q, cache, stable=False)

    for length in lengths:
        ln = jnp.int32(length)
        rows = {"shape": f"b{b}_h{h}_S{S}_d{d}_{jnp.dtype(dtype).name}",
                "length": length}
        # cache rides as an ARGUMENT (a closure would bake the whole buffer
        # into the program as a constant)
        rows["dense_us"] = round(timed_scan(
            lambda qq, cc: cached_attend(qq, cc, ln, use_kernel=False),
            (q, cache), k=64) * 1e6, 1)
        if single_ok:
            rows["single_us"] = round(timed_scan(
                lambda qq, cc: decode_attend_kernel(qq, cc, ln),
                (q, cache), k=64) * 1e6, 1)
        for blk in blks:
            if S % blk:
                continue
            rows[f"chunk{blk}_us"] = round(timed_scan(
                lambda qq, cc, bb=blk: decode_attend_kernel_chunked(
                    qq, cc, ln, blk=bb),
                (q, cache), k=64) * 1e6, 1)
        print(json.dumps(rows), flush=True)


def main():
    # small-model long cache: single-block still fits
    run(64, 8, 1280, 64, jnp.bfloat16, lengths=(320, 640, 1280))
    run(64, 8, 1280, 64, jnp.int8, lengths=(320, 640, 1280))
    # flagship-head long cache: single-block busts its budget
    run(16, 14, 2560, 128, jnp.bfloat16, lengths=(640, 1280, 2560))
    run(16, 14, 2560, 128, jnp.int8, lengths=(640, 1280, 2560))


if __name__ == "__main__":
    main()

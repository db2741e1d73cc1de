#!/usr/bin/env python
"""Flash-vs-dense attention microbenchmark on the attached chip.

Times fwd+bwd through the two attention cores the Transformer can use —
the fused dense path (ops/attention.py:attend) and the Pallas flash kernel
with block skipping (ops/flash_attention.py) — across sequence lengths,
mask families, and block sizes. Records the crossover table that justifies
``use_pallas`` (VERDICT r1 #5).

Run: python scripts/bench_flash.py [--seqs 512,1024,2048,4096]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(grad_fn, q, k, v, iters=100, warmup=2):
    """Per-iteration time of fwd+bwd, measured as ONE dispatched scan of
    ``iters`` chained calls, so no host dispatch sits between the kernels
    being measured."""
    eps = jnp.asarray(1e-30, q.dtype)  # runtime value: blocks DCE/folding

    @jax.jit
    def many(q, k, v, eps):
        def body(carry, _):
            q, k, v = carry
            gq, gk, gv = grad_fn(q, k, v)
            return (q + eps * gq, k + eps * gk, v + eps * gv), ()
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=iters)
        return jnp.sum(q.astype(jnp.float32))  # scalar: cheap to pull

    for _ in range(warmup):
        r = many(q, k, v, eps)
    np.asarray(jax.device_get(r))  # sync: pull the scalar
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        r = many(q, k, v, eps)
        np.asarray(jax.device_get(r))
        best = min(best, time.perf_counter() - t0)
    return best / iters


def masks_for(kind, n, text_len, fmap):
    """(numpy mask, structured spec) per kind."""
    if kind == "full":
        return None, None
    from dalle_tpu.ops.attn_masks import axial_mask, conv_like_mask
    if kind == "axial_row":
        return (np.asarray(axial_mask(text_len, fmap, axis=0)),
                ("axial", text_len, fmap, 0))
    if kind == "conv_like":
        return (np.asarray(conv_like_mask(text_len, fmap, kernel_size=5)),
                ("conv", text_len, fmap, 5, 1))
    raise ValueError(kind)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=str, default="512,1024,2048,4096")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim_head", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--blocks", type=str, default="128,256,512")
    ap.add_argument("--dtype", type=str, default="bfloat16")
    args = ap.parse_args()

    from dalle_tpu.ops.attention import attend
    from dalle_tpu.ops.flash_attention import flash_attention, sparsity_fraction

    dt = jnp.dtype(args.dtype)
    rows = []
    for n in (int(s) for s in args.seqs.split(",")):
        # DALL·E geometry: 256 text tokens + fmap² image tokens
        fmap = int(round((n - 256) ** 0.5))
        n_eff = 256 + fmap * fmap
        key = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (args.batch, args.heads, n_eff,
                                      args.dim_head), dt)
                   for i in range(3))

        for kind in ("full", "axial_row", "conv_like"):
            mask, spec = masks_for(kind, n_eff, 256, fmap)
            if mask is not None and mask.shape[0] < n_eff:
                continue

            def dense_loss(q, k, v):
                o = attend(q, k, v, causal=True, softmax_f32=False,
                           static_mask=None if mask is None
                           else jnp.asarray(mask[:n_eff, :n_eff]))
                return jnp.sum(o.astype(jnp.float32))

            dense = jax.grad(dense_loss, argnums=(0, 1, 2))
            try:
                t_dense = timeit(dense, q, k, v)
            except Exception as e:  # noqa: BLE001 - sweep point: a
                # rejected config becomes an error row, not an aborted sweep
                print(json.dumps({"seq": n_eff, "mask": kind, "dense_error":
                                  str(e)[:120]}), flush=True)
                t_dense = None

            best = None
            for blk in (int(b) for b in args.blocks.split(",")):
                if blk > n_eff:
                    continue

                def flash_loss(q, k, v, _blk=blk):
                    o = flash_attention(q, k, v, causal=True,
                                        mask=None if mask is None else
                                        mask[:n_eff, :n_eff],
                                        mask_spec=spec,
                                        block_q=_blk, block_k=_blk)
                    return jnp.sum(o.astype(jnp.float32))

                fl = jax.grad(flash_loss, argnums=(0, 1, 2))
                try:
                    t = timeit(fl, q, k, v)
                except Exception as e:  # noqa: BLE001 - sweep point
                    print(json.dumps({"seq": n_eff, "mask": kind, "block": blk,
                                      "error": str(e)[:120]}), flush=True)
                    continue
                if best is None or t < best[1]:
                    best = (blk, t)

            frac = sparsity_fraction(
                n_eff, best[0] if best else 128, best[0] if best else 128,
                mask if mask is None else mask[:n_eff, :n_eff])
            row = {"seq": n_eff, "mask": kind,
                   "dense_ms": None if t_dense is None else round(t_dense * 1e3, 3),
                   "flash_ms": None if best is None else round(best[1] * 1e3, 3),
                   "best_block": None if best is None else best[0],
                   "block_frac": round(frac, 3),
                   "speedup": None if (best is None or t_dense is None)
                   else round(t_dense / best[1], 2)}
            rows.append(row)
            print(json.dumps(row), flush=True)

    print("\n| seq | mask | dense ms | flash ms | best block | blocks visited | speedup |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['seq']} | {r['mask']} | {r['dense_ms']} | {r['flash_ms']} "
              f"| {r['best_block']} | {r['block_frac']} | {r['speedup']}x |")


if __name__ == "__main__":
    main()

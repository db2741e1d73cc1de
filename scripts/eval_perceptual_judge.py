#!/usr/bin/env python
"""Referee the shipped tiny perceptual net against its alternatives with
judges NONE of the arms trained on (VERDICT r3 next #4).

Arms (identical small VQGANs on synthetic shapes, disc off, same data order):
  * tiny@0.22      — the shipped tiny-LPIPS at scale-matched weight (its
                     metric is ~4.5x stronger per unit weight than ones-init)
  * onesinit@1.0   — the offline ones-init fallback ('vgg' with no weights)
  * none           — no perceptual term (pixel + quant losses only)

Judges (held-out shapes, lower = better recon under that judge):
  * vgg-lpips      — the golden-imported REAL VGG16 LPIPS
                     (models/lpips.py:load_torch_weights) when
                     ``--vgg_pth``/``--lins_pth`` point at local torchvision
                     vgg16 + taming vgg.pth state dicts. This sandbox has no
                     network and ships no VGG weights, so the row prints
                     "unavailable" here — the harness is complete and runs
                     the VERDICT's exact experiment wherever the weights
                     exist.
  * judge-net      — an INDEPENDENTLY trained tiny-LPIPS (different seed,
                     different distortion draw order, trained fresh in this
                     run) — same family as the trainee but none of the arms
                     optimized against ITS weights.
  * ssim           — structural similarity (closed-form, training-free).

Usage: python scripts/eval_perceptual_judge.py [--steps 600]
       [--vgg_pth vgg16.pth --lins_pth vgg.pth]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def ssim(a, b, data_range=2.0):
    """Mean SSIM over NHWC batches (7x7 uniform window, standard constants)."""
    from jax import numpy as jnp

    k = jnp.ones((7, 7, 1, 1), jnp.float32) / 49.0
    k = jnp.tile(k, (1, 1, 1, a.shape[-1]))

    def filt(x):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1])

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    mu_a, mu_b = filt(a), filt(b)
    var_a = filt(a * a) - mu_a ** 2
    var_b = filt(b * b) - mu_b ** 2
    cov = filt(a * b) - mu_a * mu_b
    c1, c2 = (0.01 * data_range) ** 2, (0.03 * data_range) ** 2
    s = ((2 * mu_a * mu_b + c1) * (2 * cov + c2) /
         ((mu_a ** 2 + mu_b ** 2 + c1) * (var_a + var_b + c2)))
    return float(jnp.mean(s))


def train_arm(name, perceptual_net, weight, train_imgs, steps, batch):
    from dalle_tpu.config import (MeshConfig, OptimConfig, TrainConfig,
                                  VQGANConfig)
    from dalle_tpu.models.gan import GANLossConfig
    from dalle_tpu.train.trainer_vqgan import VQGANTrainer

    cfg = VQGANConfig(embed_dim=32, n_embed=256, z_channels=32, resolution=64,
                      ch=32, ch_mult=(1, 2, 2), num_res_blocks=1,
                      attn_resolutions=())
    tc = TrainConfig(batch_size=batch, checkpoint_dir=f"/tmp/pjudge_{name}",
                     preflight_checkpoint=False, mesh=MeshConfig(dp=1),
                     metrics_every=200, seed=0,
                     optim=OptimConfig(learning_rate=2e-4))
    lc = GANLossConfig(disc_start=10 ** 9, perceptual_weight=weight,
                       perceptual_net=perceptual_net)
    tr = VQGANTrainer(cfg, tc, loss_cfg=lc)
    rng = np.random.RandomState(0)          # same data order in every arm
    n = len(train_imgs)
    for _ in range(steps):
        tr.train_step(train_imgs[rng.randint(0, n, batch)])
    return tr


def train_judge_net(seed=12345):
    """A fresh tiny-LPIPS nobody trained against: same recipe as
    scripts/train_perceptual.py but a different seed (fresh init, fresh
    distortion draws)."""
    import jax.numpy as jnp
    from dalle_tpu.data.synthetic import ShapesDataset
    from dalle_tpu.models.lpips import LPIPS, TINY_SLICES
    from train_perceptual import (COLORS, SCALES, SHAPES, rank_accuracy,
                                  train_lins, train_trunk)

    ds = ShapesDataset(image_size=64, variants=6, seed=0)
    samples = [ds[i] for i in range(len(ds))]
    images01 = jnp.asarray(np.stack([s.image for s in samples]),
                           jnp.float32) / 255.0
    shape_ids = {s: i for i, s in enumerate(SHAPES)}
    color_ids = {c: i for i, c in enumerate(COLORS)}
    scale_ids = {s: i for i, s in enumerate(SCALES)}
    labels = (np.array([shape_ids[s.label[1]] for s in samples]),
              np.array([color_ids[s.label[0]] for s in samples]),
              np.array([scale_ids[s.label[2]] for s in samples]))
    images = images01 * 2.0 - 1.0
    trunk = train_trunk(images, labels, steps=600, batch=64, seed=seed)
    model = LPIPS(slices=TINY_SLICES)
    params = jax.device_get(model.init(jax.random.PRNGKey(seed),
                                       images[:2], images[:2]))
    params["params"]["vgg"] = jax.device_get(trunk)["params"]
    params = train_lins(model, params, images01, steps=500, batch=32,
                        seed=seed + 1)
    acc = rank_accuracy(model, params, images01, seed=seed + 2)
    print(f"judge-net held-out 2AFC: {acc:.3f}", flush=True)
    return model, params


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vgg_pth", type=str, default=None,
                    help="torchvision vgg16 state_dict (.pth) for the real "
                         "VGG-LPIPS judge")
    ap.add_argument("--lins_pth", type=str, default=None,
                    help="taming vgg.pth lin-head state_dict")
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    from dalle_tpu.data.synthetic import ShapesDataset

    ds = ShapesDataset(image_size=64, variants=6, seed=0)
    imgs = np.stack([ds[i].image for i in range(len(ds))])
    imgs = imgs.astype(np.float32) / 127.5 - 1.0
    perm = np.random.RandomState(42).permutation(len(imgs))
    test, train = imgs[perm[:32]], imgs[perm[32:]]

    arms = [("tiny@0.22", "tiny", 0.22), ("onesinit@1.0", "vgg", 1.0),
            ("none", "none", 0.0)]
    recons = {}
    for name, net, w in arms:
        tr = train_arm(name.split("@")[0], net, w, train, args.steps,
                       args.batch)
        recons[name] = np.asarray(jax.device_get(tr.reconstruct(test)))
        print(f"arm {name}: trained {args.steps} steps", flush=True)

    judges = {}

    # real VGG-LPIPS (the VERDICT judge) — when weights are available
    if args.vgg_pth:
        import torch
        from dalle_tpu.models.lpips import init_lpips, load_torch_weights
        vgg_state = torch.load(args.vgg_pth, map_location="cpu")
        lin_state = (torch.load(args.lins_pth, map_location="cpu")
                     if args.lins_pth else {})
        model, params = init_lpips(jax.random.PRNGKey(0), image_size=64)
        params = load_torch_weights(params, vgg_state, lin_state)
        judges["vgg_lpips"] = lambda r, m=model, p=params: float(jnp.mean(
            m.apply(p, jnp.asarray(r), jnp.asarray(test))))
    else:
        print("vgg-lpips judge: unavailable (no --vgg_pth; this sandbox has "
              "no network and no local VGG weights)", flush=True)

    jm, jp = train_judge_net()
    judges["judge_net"] = lambda r: float(jnp.mean(
        jm.apply(jp, jnp.asarray(r), jnp.asarray(test))))
    judges["ssim"] = lambda r: ssim(r, test)
    judges["l1"] = lambda r: float(np.mean(np.abs(r - test)))

    table = {}
    for name in recons:
        table[name] = {j: round(f(recons[name]), 5)
                       for j, f in judges.items()}
        print(json.dumps({"arm": name, **table[name]}), flush=True)

    def best(judge, bigger_better=False):
        vals = {a: table[a][judge] for a in table}
        pick = max(vals, key=vals.get) if bigger_better else min(vals, key=vals.get)
        return pick

    verdict = {"judge_net_best": best("judge_net"),
               "ssim_best": best("ssim", bigger_better=True),
               "tiny_beats_onesinit_judge_net":
                   table["tiny@0.22"]["judge_net"]
                   < table["onesinit@1.0"]["judge_net"]}
    if "vgg_lpips" in judges:
        verdict["vgg_best"] = best("vgg_lpips")
    print(json.dumps({"metric": "perceptual_judge", **verdict}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Input-pipeline throughput: can one host core feed one chip? (VERDICT r4 #3)

Measures the host-side data path the 1.4B trainer consumes — tar-shard
streaming → JPEG decode+resize → BPE tokenize → batch, with and without
decode workers / prefetch — in imgs/s per host core, against the flagship's
measured consumption rate (BENCH: ~13.6k tok/s/chip ÷ 513 tok/sample ≈ 26.6
samples/s/chip). Prints one JSON line per stage and a summary line.

Reference bar: the wds chain this replaces (legacy/train_dalle.py:365-423 —
a naive PIL loop the SURVEY §7 hard-parts list flags as unable to feed a
pod).

Synthetic shards: 256×256 JPEGs (web-scrape scale) + caption txt, written
with data/webdataset.write_shards. No network, no torch.
"""

import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_shards(root: str, n_samples: int = 2048,
                 samples_per_shard: int = 512, src_px: int = 256):
    """Deterministic JPEG+txt shards; returns the shard paths."""
    from PIL import Image

    from dalle_tpu.data.webdataset import write_shards

    rng = np.random.RandomState(0)
    words = ("red green blue small large circle square star over under a the"
             .split())

    def samples():
        for i in range(n_samples):
            # structured noise compresses like a photo, not like white noise
            base = rng.randint(0, 255, (8, 8, 3), np.uint8)
            img = Image.fromarray(base).resize((src_px, src_px),
                                               Image.BILINEAR)
            buf = io.BytesIO()
            img.save(buf, "JPEG", quality=90)
            cap = " ".join(rng.choice(words, 12))
            yield {"__key__": f"{i:06d}", "jpg": buf.getvalue(), "txt": cap}

    os.makedirs(root, exist_ok=True)
    return write_shards(samples(), os.path.join(root, "shard-{:04d}.tar"),
                        samples_per_shard)


def timed(name, iterator, n_samples, batch_size=1, extra=None):
    t0 = time.perf_counter()
    seen = 0
    for item in iterator:
        seen += batch_size
        if seen >= n_samples:
            break
    dt = time.perf_counter() - t0
    rate = seen / dt
    line = {"stage": name, "samples": seen, "secs": round(dt, 2),
            "imgs_per_s": round(rate, 1)}
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)
    return rate


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default="/tmp/wds_bench")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--image_size", type=int, default=128)
    ap.add_argument("--consumption_tok_s", type=float, default=13622.0,
                    help="flagship chip consumption (driver bench r04, "
                    "2026-07-31; see ROADMAP.md 'State of the records')")
    ap.add_argument("--seq_len", type=int, default=513)
    args = ap.parse_args()

    from dalle_tpu.data.webdataset import (WebDataset, iter_tar_samples,
                                           reraise)
    from dalle_tpu.text.tokenizer import get_tokenizer

    marker = os.path.join(args.root, f"ready_{args.n}")
    if not os.path.exists(marker):
        t0 = time.perf_counter()
        build_shards(args.root, args.n)
        open(marker, "w").write("ok")
        print(json.dumps({"stage": "build_shards", "samples": args.n,
                          "secs": round(time.perf_counter() - t0, 2)}),
              flush=True)

    shards = sorted(
        os.path.join(args.root, f) for f in os.listdir(args.root)
        if f.endswith(".tar"))

    # 1. raw tar streaming (no decode)
    def raw():
        for s in shards:
            yield from iter_tar_samples(s, reraise)
    timed("tar_stream", raw(), args.n)

    # 2. + JPEG decode + resize, single-threaded
    r_dec = timed(
        "decode_1thread",
        iter(WebDataset(shards, handler=reraise)
             .decode(image_size=args.image_size)),
        args.n)

    # 3. + decode on 4 worker threads (PIL releases the GIL in codecs —
    #    on a 1-core box this mostly measures that the overlap machinery
    #    doesn't cost; on a real multi-core host it scales)
    r_dec4 = timed(
        "decode_4workers",
        iter(WebDataset(shards, handler=reraise)
             .decode(image_size=args.image_size, workers=4)),
        args.n)

    # 4. BPE tokenization alone (batch of captions per call, the trainer's
    #    encode_batch shape)
    tok = get_tokenizer("simple")
    caps = [" ".join(["a red circle over the blue square"] * 2)] * 256
    t0 = time.perf_counter()
    reps = 40
    for _ in range(reps):
        tok.tokenize(caps, 256, truncate_text=True)
    bpe_rate = reps * len(caps) / (time.perf_counter() - t0)
    print(json.dumps({"stage": "bpe_tokenize", "caps_per_s":
                      round(bpe_rate, 1)}), flush=True)

    # 5. full chain exactly as scripts/train_dalle.py builds it: decode →
    #    to pair → shuffle → batch → prefetch thread → tokenize per batch
    bsz = 64
    wds = (WebDataset(shards, handler=reraise, shuffle_shards=True,
                      repeat=True)
           .decode(image_size=args.image_size, workers=4)
           .map(lambda s: (s["jpg"], s["txt"]))
           .shuffle(256)
           .batched(bsz))

    def full():
        for imgs, capss in wds.prefetch():
            text = tok.tokenize(list(capss), 256, truncate_text=True)
            yield np.stack(imgs), text
    r_full = timed("full_pipeline_b64", full(), args.n, batch_size=bsz)

    need = args.consumption_tok_s / args.seq_len
    print(json.dumps({
        "metric": "input_pipeline_imgs_per_s_per_core",
        "value": round(r_full, 1), "unit": "imgs/s/core",
        "chip_consumption_imgs_per_s": round(need, 1),
        "margin_x": round(r_full / need, 2),
        "decode_1t": round(r_dec, 1), "decode_4w": round(r_dec4, 1),
        "bpe_caps_per_s": round(bpe_rate, 1),
    }), flush=True)


if __name__ == "__main__":
    main()

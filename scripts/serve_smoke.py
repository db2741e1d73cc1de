#!/usr/bin/env python
"""Continuous-batching serve smoke — the CI gate for dalle_tpu/serve.

A short offered-load run on a tiny model (CPU mesh) asserting the three
serving contracts that must never drift:

  * token-exactness — every completed request's tokens equal single-request
    ``generate_images_tokens(text[None], PRNGKey(seed))`` bitwise, despite
    ragged admission through shared-cache slots;
  * work conservation — slot occupancy stays ≥ 90% at iterations where the
    queue still held requests (continuous batching's whole point), and the
    queue drains (every submitted request completes, FIFO admission order);
  * observability — tracing captures one ``serve/request`` +
    ``serve/request_ttft`` span per request with sane timings, and the
    queue-depth / occupancy gauges + token counters are live.

A second phase reruns the workload through the PAGED engine (graftpage,
``kv_block_tokens=4``): exactness must survive block remaps, radix prefix
hits and COW forks, repeated prompts must actually hit the radix cache,
and — after one warmup run — a fresh admission mix must trigger ZERO XLA
compiles (the page table is device data, never program shape).

Artifacts (smoke.json, serve_spans.jsonl) land in ``--outdir`` — the dir
ci.yml uploads. Run: JAX_PLATFORMS=cpu python scripts/serve_smoke.py
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--outdir", type=str, default="serve_artifacts")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--n_requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--precision", choices=("int8w", "float32"),
                    default="int8w",
                    help="serving precision under test (default: the "
                         "engine's int8-weights + int8-KV production "
                         "default; references run the same mode, so the "
                         "exactness bar stays bitwise)")
    args = ap.parse_args(argv)
    os.makedirs(args.outdir, exist_ok=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu import obs
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.dalle import DALLE, init_dalle
    from dalle_tpu.serve import DecodeEngine, RequestQueue

    cfg = DalleConfig(num_text_tokens=32, text_seq_len=6, dim=64, depth=2,
                      heads=2, dim_head=32, image_size=16,
                      image_vocab_size=24, image_fmap_size=4)
    model, params = init_dalle(cfg, jax.random.PRNGKey(args.seed), batch=2)
    if args.precision == "int8w":
        # the serving default (DalleWithVae.serve_engine): int8 matmul
        # kernels + per-channel scales, everything else bf16, int8 KV
        from dalle_tpu.ops.quantize_weights import quantize_params_int8
        params = quantize_params_int8(params)
        cache_dtype = jnp.int8
    else:
        cache_dtype = jnp.float32
    rng = np.random.RandomState(args.seed)
    texts = [rng.randint(1, 20, (cfg.text_seq_len,)).astype(np.int32)
             for _ in range(args.n_requests)]

    # sequential references, one per request under its own key — same
    # params tree and cache dtype as the engine, so exactness is bitwise
    refs = {}
    for i, t in enumerate(texts):
        ids = model.apply(params, jnp.asarray(t[None]),
                          jax.random.PRNGKey(1000 + i),
                          cache_dtype=cache_dtype,
                          method=DALLE.generate_images_tokens)
        refs[i] = np.asarray(ids[0])

    tracer = obs.configure()
    q = RequestQueue()
    # offered load: a burst up front plus staggered submissions from a
    # producer thread, so admission interleaves with mid-flight decode
    for i in range(args.slots + 1):
        q.submit(texts[i], seed=1000 + i, request_id=i)

    def producer():
        for i in range(args.slots + 1, args.n_requests):
            time.sleep(0.02)
            q.submit(texts[i], seed=1000 + i, request_id=i)
        q.close()

    th = threading.Thread(target=producer)
    th.start()
    eng = DecodeEngine(model, params, slots=args.slots,
                       cache_dtype=cache_dtype)
    t0 = time.perf_counter()
    done = eng.run(q)
    wall = time.perf_counter() - t0
    th.join()

    failures = []

    def check(ok, msg):
        print(("PASS " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    check(len(done) == args.n_requests,
          f"drain: {len(done)}/{args.n_requests} requests completed")
    exact = all(bool((c.tokens == refs[c.request_id]).all()) for c in done)
    check(exact, "token-exact vs single-request generation for every "
          "request (any admission order)")
    occ = eng.stats.occupancy_while_queued
    check(occ >= 0.90, f"slot occupancy while queue nonempty: {occ:.3f} "
          ">= 0.90")
    check(all(c.first_token_at >= c.admitted_at >= c.submitted_at
              and c.completed_at >= c.first_token_at for c in done),
          "per-request timestamps are ordered "
          "(submit <= admit <= first token <= complete)")

    spans = tracer.snapshot_spans()
    by_name = {}
    for name, rel, dur, tid, depth, sargs, *_ in spans:
        by_name.setdefault(name, []).append((dur, sargs))
    for want in ("serve/request", "serve/request_ttft"):
        rows = by_name.get(want, [])
        ids = sorted(a["request_id"] for _, a in rows)
        check(ids == list(range(args.n_requests)),
              f"{want}: one span per request with request_id args")
        check(all(0 <= d <= wall + 1 for d, _ in rows),
              f"{want}: durations within the run wall clock")
    metrics = obs.metrics_snapshot()
    check(metrics.get("serve.requests_completed_total") == len(done),
          "serve.requests_completed_total counter matches completions")
    check(metrics.get("serve.tokens_emitted_total", 0)
          >= args.n_requests * cfg.image_seq_len,
          "serve.tokens_emitted_total covers every request's tokens")

    # ----- phase 2: paged KV (graftpage) ---------------------------------
    # the same workload through the paged engine: tokens must stay bitwise
    # the sequential references through block remaps, radix prefix hits and
    # COW forks — and once one warmup run has compiled the fixed program
    # set, a fresh run with a DIFFERENT admission mix (staggered arrivals,
    # repeated prompts, pool churn) must compile NOTHING. That is the
    # no-recompile invariant: the page table is data, never shape.
    counter = obs.install_compile_counter()
    # pool sized for live rows PLUS radix residency: the default (slots ×
    # blocks/slot) keeps HBM parity with the dense slab but leaves zero
    # headroom for cached prefixes, so every resident would be evicted
    # before its repeat arrives — the smoke wants hits to be demonstrable
    bt = 4
    blocks_per_slot = -(-cfg.total_seq_len // bt)
    peng = DecodeEngine(model, params, slots=args.slots,
                        cache_dtype=cache_dtype, kv_block_tokens=bt,
                        kv_pool_blocks=(args.slots + args.n_requests)
                        * blocks_per_slot)
    # warmup must touch EVERY program in the fixed set: a burst (bulk
    # refill + step scan), a trickled fresh prompt (the block-width prefill
    # chunks), and a trickled repeat (radix hit -> COW fork + the width-1
    # recompute chunk)
    warm = {2: (4, 3000), 3: (0, 3001)}        # id -> (text idx, seed)
    warm_refs = {}
    for rid, (src, seed) in warm.items():
        ids = model.apply(params, jnp.asarray(texts[src][None]),
                          jax.random.PRNGKey(seed), cache_dtype=cache_dtype,
                          method=DALLE.generate_images_tokens)
        warm_refs[rid] = np.asarray(ids[0])
    wq = RequestQueue()
    for i in range(2):
        wq.submit(texts[i], seed=1000 + i, request_id=i)

    def warm_producer():
        for rid, (src, seed) in warm.items():
            time.sleep(0.05)
            wq.submit(texts[src], seed=seed, request_id=rid)
        wq.close()

    wth = threading.Thread(target=warm_producer)
    wth.start()
    wdone = peng.run(wq)
    wth.join()
    check(all(bool((c.tokens == (warm_refs[c.request_id]
                                 if c.request_id in warm_refs
                                 else refs[c.request_id])).all())
              for c in wdone),
          "paged warmup: token-exact vs the sequential references")
    warm_hit_tok = peng.stats.prefix_hit_tokens   # stats reset per run()
    # repeat prompts ride NEW seeds — a radix hit shares prompt KV between
    # requests whose decodes then diverge; references are sequential and
    # fully independent, computed BEFORE the zero-compile window opens
    dup_refs = {}
    for j, src in enumerate((2, 3)):
        rid, seed = args.n_requests + j, 4000 + j
        ids = model.apply(params, jnp.asarray(texts[src][None]),
                          jax.random.PRNGKey(seed), cache_dtype=cache_dtype,
                          method=DALLE.generate_images_tokens)
        dup_refs[rid] = (src, np.asarray(ids[0]), seed)
    compiles_before = counter.count
    q2 = RequestQueue()
    for i in range(2, args.slots + 3):
        q2.submit(texts[i], seed=1000 + i, request_id=i)

    def paged_producer():
        for i in range(args.slots + 3, args.n_requests):
            time.sleep(0.02)
            q2.submit(texts[i], seed=1000 + i, request_id=i)
        for rid, (src, _, seed) in dup_refs.items():
            time.sleep(0.02)
            q2.submit(texts[src], seed=seed, request_id=rid)
        q2.close()

    th2 = threading.Thread(target=paged_producer)
    th2.start()
    pdone = peng.run(q2)
    th2.join()
    paged_compiles = counter.count - compiles_before
    check(len(pdone) == args.n_requests,
          f"paged drain: {len(pdone)}/{args.n_requests} requests completed")
    pexact = all(bool((c.tokens == (dup_refs[c.request_id][1]
                                    if c.request_id in dup_refs
                                    else refs[c.request_id])).all())
                 for c in pdone)
    check(pexact, "paged: token-exact vs sequential references (radix "
          "hits and COW forks included)")
    check(peng.stats.radix_full_hits >= 2,
          f"paged: repeated prompts hit the radix cache "
          f"({peng.stats.radix_full_hits} full hits)")
    check(paged_compiles == 0,
          f"paged no-recompile invariant: {paged_compiles} XLA compiles "
          "after warmup (page-table updates are data, not shape)")
    kv = peng.kv_stats()
    m2 = obs.metrics_snapshot()
    # the counter is cumulative across serve loops; the radix ledger and
    # EngineStats reset per run — the warmup run's hits are part of the
    # counter's total
    check(m2.get("kv.prefix_hit_tokens_total", 0)
          == warm_hit_tok + kv["prefix_hit_tokens"]
          and kv["prefix_hit_tokens"] > 0,
          "kv.prefix_hit_tokens_total counter matches the radix ledger")

    n_spans = obs.export_spans_jsonl(
        os.path.join(args.outdir, "serve_spans.jsonl"))
    summary = {
        "requests": args.n_requests, "slots": args.slots,
        "precision": args.precision,
        "wall_s": round(wall, 3), "steps": eng.stats.steps,
        "refills": eng.stats.refills,
        "occupancy_while_queued": round(occ, 4),
        "token_exact": exact, "spans_exported": n_spans,
        "paged": {"token_exact": pexact, "compiles_after_warmup":
                  paged_compiles, "radix_full_hits":
                  peng.stats.radix_full_hits, "prefix_hit_tokens":
                  kv["prefix_hit_tokens"], "cow_copies": kv["cow_copies"],
                  "pages_evicted": peng.stats.pages_evicted},
        "completed_per_s": round(len(done) / wall, 3),
        "p50_latency_s": round(float(np.median(
            [c.latency_s for c in done])), 4) if done else None,
        "failures": failures,
    }
    with open(os.path.join(args.outdir, "smoke.json"), "w") as fh:
        json.dump(summary, fh, indent=2)
    obs.disable()
    print(json.dumps({"metric": "serve_smoke", **summary}), flush=True)
    if failures:
        print(f"serve_smoke: FAILED ({len(failures)} checks)")
        return 1
    print("serve_smoke: GREEN")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on ONE TPU chip through the entry points a user
calls, at the full width and depth of DALL·E-1.4B (24L, 14 heads x 128, dim
1792, CLIP text vocab 49,408, 256 text + 256 image tokens, image vocab 8,192
— widths no published model has; ROADMAP.md queues their removal), with
random weights made from a seed:

  train_1p4b   ``DalleTrainer.fit`` with the flagship cell's recipe (Adafactor,
               grad_clip_norm 0.5, loss_chunk 128, bf16 scores, no remat,
               batch 8): a scanned ``train_steps`` dispatch plus single
               steps on one repeated batch. Loss finite at every step and
               lower at the end, parameters changed, zero compiles once
               both programs are warm.
  train_small  DALL·E-small (12L/8H/512d), ``use_pallas="auto"``, batch 64,
               three steps: the one training configuration in which the
               fused Pallas kernel is selected. The lowered step must hold
               the Mosaic custom call, and its first-step loss must agree
               with ``use_pallas=False`` on the same batch.
  serve        ``DalleWithVae.serve_engine(slots=8)`` (int8 weights + int8
               KV) behind a ``RequestQueue``: a dozen tokenized prompts,
               ``engine.run``, ``vae.decode`` on the grids, two requests
               checked against ``generate_images_tokens`` on the engine's
               own params tree. Then its paged twin (``kv_block_tokens``,
               pool sized to the dense slabs) with repeated prompts, same
               checks plus radix hits.

``--multichip`` (four chips, run by hand) runs the 1.4B train step on a
fsdp=2 x tp=2 mesh and, after it, the same batch and seed on one device,
and nothing else.

Process model: this parent NEVER imports jax. Each phase is a child process
(``python -c "import chip_smoke; chip_smoke.child_main(NAME, n)"``) run one
after another — the 1.4B trainer and the 1.4B server each need most of the
16 GB, and a process that exits is the one sure way to give the chip back. The parent takes the device line from the
children. Every child refuses to start without a TPU; a phase that raises
makes the run exit non-zero. All phases share the one compile cache
(``dalle_tpu.utils.misc.enable_compilation_cache``); each prints what it
compiled cold and what it read back.

The last line of standard output is the contract's:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

PHASES = ("train_1p4b", "train_small", "serve")
MULTICHIP_PHASES = ("multichip_sharded", "multichip_single")
PHASE_TIMEOUT_S = 900
# serve: engine tokens are not bitwise generate_images_tokens' on the chip
# (docs/SERVING.md "The exactness contract on the chip"). Fed back through
# that path teacher-forced, it must sample the same token at this share of
# positions, and no engine token may sit further than this below its winner
# in perturbed-logit units (logit / temperature + gumbel).
TEACHER_FORCED_MIN_AGREE = 0.90
TEACHER_FORCED_MAX_GAP = 3.0

# DALL·E-1.4B at 14 x 128 (the benchmark's flagship is rudalle_malevich.json)
FLAGSHIP = dict(
    num_text_tokens=49408, text_seq_len=256, dim=1792, depth=24, heads=14,
    dim_head=128, image_size=128, image_vocab_size=8192, image_fmap_size=16,
    attn_softmax_f32=False, loss_chunk=128, use_remat=False)
# DALL·E-small (__graft_entry__.entry; benchmarks/configs/dalle_small.json)
SMALL = dict(
    num_text_tokens=10000, text_seq_len=256, dim=512, depth=12, heads=8,
    dim_head=64, image_size=128, image_vocab_size=8192, image_fmap_size=16,
    attn_softmax_f32=False, use_pallas="auto")

PROMPTS = (
    "an armchair in the shape of an avocado",
    "a red cube on top of a blue sphere",
    "a watercolor painting of a fox in the snow",
    "the skyline of a city made of glass at dusk",
    "a small green turtle reading a newspaper",
    "a bowl of soup that looks like a galaxy",
    "a stained glass window of a hummingbird",
    "a lighthouse on a cliff during a storm",
    "an illustration of a snail made of a harp",
    "a photograph of an orange bicycle by a canal",
    "a tiny house built inside a light bulb",
    "a map of an island shaped like a cat",
)


# --------------------------------------------------------------------------
# children: everything below the parent section imports jax
# --------------------------------------------------------------------------

def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok, why) -> None:
    """A check of the smoke itself: raises (also under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {why}")


def require_tpu() -> dict:
    """The device as jax reports it; exits non-zero unless it is a TPU."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU and jax found platform "
                 f"{d.platform!r}: nothing was run.")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class CompileLedger:
    """Backend compiles (count, seconds) and persistent-cache hits/misses in
    this process, from jax.monitoring."""

    def __init__(self):
        import jax
        from dalle_tpu.obs.device import BACKEND_COMPILE_EVENT
        self._event = BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event == self._event:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        import jax
        return (f"compile: {self.compiles} programs, {self.compile_s:.1f} s in "
                f"the backend (cold where missed); cache {self.hits} hits / "
                f"{self.misses} misses in "
                f"{jax.config.jax_compilation_cache_dir}")


def peak_bytes() -> int:
    """Peak bytes as ``device.memory_stats()`` has them: buffers plus what
    loaded programs reserved for their temporaries."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def mosaic_calls(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


def synthetic_batch(cfg, batch: int, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    text = rng.randint(1, cfg.num_text_tokens, (batch, cfg.text_seq_len))
    ids = rng.randint(0, cfg.image_vocab_size, (batch, cfg.image_seq_len))
    return text.astype(np.int32), ids.astype(np.int32)


class _Losses:
    """metrics_writer for fit(): keeps (step, loss) of every record."""

    def __init__(self):
        self.rows = []
        self.finite = True

    def log(self, step, metrics):
        # a scanned group reports its last step's loss and the group mean
        self.rows.append((int(step), float(metrics["loss"])))
        self.finite = self.finite and all(
            math.isfinite(float(metrics[k]))
            for k in ("loss", "loss_mean") if k in metrics)


def make_trainer(model_kw: dict, *, batch: int, name: str, optimizer: str,
                 mesh_cfg=None, devices=None, scan_steps: int = 1):
    from dalle_tpu.config import (DalleConfig, MeshConfig, OptimConfig,
                                  TrainConfig)
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    cfg = DalleConfig(**model_kw)
    mesh_cfg = mesh_cfg or MeshConfig()
    tc = TrainConfig(
        batch_size=batch, checkpoint_dir=os.path.join(OUT, f"ckpt_{name}"),
        preflight_checkpoint=False, save_every_steps=0, log_every=1,
        metrics_every=1, scan_steps=scan_steps, mesh=mesh_cfg,
        optim=OptimConfig(optimizer=optimizer, grad_clip_norm=0.5))
    trainer = DalleTrainer(cfg, tc, mesh=build_mesh(mesh_cfg,
                                                    devices=devices))
    return cfg, trainer


def lowered_step_text(trainer, text, ids) -> str:
    """StableHLO of the trainer's own jitted step at this batch."""
    import jax
    t, i = trainer._put_batch((text, ids))
    key = jax.random.fold_in(trainer.base_key, 0)
    return trainer.step_fn.lower(trainer.state, t, i, key).as_text()


def phase_train_1p4b(model_kw=FLAGSHIP, batch: int = 8) -> dict:
    """1.4B trainer through fit(): one scanned dispatch + single steps."""
    import jax
    import numpy as np
    ledger = CompileLedger()
    t0 = time.perf_counter()
    cfg, trainer = make_trainer(model_kw, batch=batch, name="1p4b",
                                optimizer="adafactor", scan_steps=2)
    say(f"[train_1p4b] {trainer.num_params / 1e9:.3f}B params, batch "
        f"{batch}, init {time.perf_counter() - t0:.1f} s")
    text, ids = synthetic_batch(cfg, batch, seed=0)
    calls = mosaic_calls(lowered_step_text(trainer, text, ids))
    say(f"[train_1p4b] attention tier in the lowered step: "
        f"{'Mosaic kernel x%d' % calls if calls else 'dense XLA'} "
        f"(use_pallas={cfg.use_pallas!r})")
    leaf0 = jax.tree.leaves(trainer.state.params)[0]
    before = np.asarray(leaf0).copy()

    losses = _Losses()
    # warm-up fit: (b, b) through the scanned train_steps, then one
    # train_step — both programs compile here
    t0 = time.perf_counter()
    trainer.fit([(text, ids)] * 3, log=say, metrics_writer=losses)
    warm_s = time.perf_counter() - t0
    warm_compiles = ledger.compiles
    say(f"[train_1p4b] warm-up fit: 3 steps in {warm_s:.1f} s, "
        f"{warm_compiles} compiles ({ledger.compile_s:.1f} s)")
    # steady fit: the same two programs again, no compile
    t0 = time.perf_counter()
    trainer.fit([(text, ids)] * 3, log=say, metrics_writer=losses)
    jax.block_until_ready(trainer.state.params)
    steady_s = time.perf_counter() - t0
    steady_compiles = ledger.compiles - warm_compiles

    # a timing stops at block_until_ready: does that wait for the device?
    # One more scanned dispatch with no metrics fetch (metrics_every), then
    # how long a scalar pull still has to wait once block_until_ready has
    # returned.
    trainer.train_cfg = trainer.train_cfg.replace(metrics_every=1000)
    t0 = time.perf_counter()
    trainer.train_steps(np.stack([text, text]), np.stack([ids, ids]))
    t1 = time.perf_counter()
    jax.block_until_ready(trainer.state.params)
    t2 = time.perf_counter()
    int(jax.device_get(trainer.state.step))     # a scalar the program wrote
    t3 = time.perf_counter()
    say(f"[train_1p4b] sync check on a 2-step dispatch: dispatch returned "
        f"after {t1 - t0:.3f} s, block_until_ready waited {t2 - t1:.3f} s, "
        f"a scalar device_get after it {t3 - t2:.4f} s")
    require(t3 - t2 < 0.05 * max(t2 - t0, 1e-3) + 0.05,
            "work was still running after block_until_ready returned")

    vals = [v for _, v in losses.rows]
    say(f"[train_1p4b] losses by record: "
        + ", ".join(f"step {s}: {v:.4f}" for s, v in losses.rows))
    say(f"[train_1p4b] memory_stats: {jax.devices()[0].memory_stats()}")
    say(f"[train_1p4b] steady fit: 3 steps in {steady_s:.2f} s (fit()'s "
        f"host rollback snapshot of the state included), "
        f"{steady_compiles} compiles; peak {peak_bytes() / 2**30:.2f} GiB")
    say("[train_1p4b] " + ledger.line())
    require(trainer._host_step == 8, f"host step {trainer._host_step} != 8")
    require(losses.finite, losses.rows)
    require(vals[-1] < vals[0],
            f"loss did not fall on a repeated batch: {vals}")
    after = np.asarray(jax.tree.leaves(trainer.state.params)[0])
    require(not np.array_equal(before, after), "parameters did not change")
    require(steady_compiles == 0, f"{steady_compiles} compiles after warm-up")
    return {"tier": "mosaic" if calls else "dense", "losses": vals,
            "cold_compile_s": round(ledger.compile_s, 1)}


def phase_train_small(model_kw=SMALL, batch: int = 64,
                      on_chip: bool = True) -> dict:
    """DALL·E-small with the kernel tier; first loss checked against dense.
    ``on_chip=False`` (a CPU rehearsal, where "auto" resolves to dense)
    only skips the assertion that the Mosaic call is in the program."""
    import numpy as np
    ledger = CompileLedger()
    cfg, trainer = make_trainer(model_kw, batch=batch, name="small",
                                optimizer="adam")
    text, ids = synthetic_batch(cfg, batch, seed=1)
    calls = mosaic_calls(lowered_step_text(trainer, text, ids))
    say(f"[train_small] attention tier in the lowered step: "
        f"{'Mosaic kernel x%d' % calls if calls else 'dense XLA'} "
        f"(use_pallas={cfg.use_pallas!r})")
    if on_chip:
        require(calls > 0, ("use_pallas='auto' did not put the fused Pallas "
                           "kernel into DALL·E-small's train step"))
    losses = _Losses()
    t0 = time.perf_counter()
    trainer.fit([(text, ids)] * 3, log=say, metrics_writer=losses)
    say(f"[train_small] 3 steps in {time.perf_counter() - t0:.1f} s "
        f"(first includes the compile); peak {peak_bytes() / 2**30:.2f} GiB")

    _, dense = make_trainer(dict(model_kw, use_pallas=False), batch=batch,
                            name="small_dense", optimizer="adam")
    require(mosaic_calls(lowered_step_text(dense, text, ids)) == 0,
            "use_pallas=False still lowered a Mosaic call")
    dense_loss = float(dense.train_step(text, ids)["loss"])
    vals = [v for _, v in losses.rows]
    say(f"[train_small] losses: {', '.join(f'{v:.4f}' for v in vals)}; "
        f"dense first step {dense_loss:.4f} "
        f"(rel diff {abs(vals[0] - dense_loss) / dense_loss:.2e})")
    say("[train_small] " + ledger.line())
    require(losses.finite and vals[-1] < vals[0], vals)
    # bf16 activations through two different attention implementations
    np.testing.assert_allclose(vals[0], dense_loss, rtol=5e-3)
    return {"tier": "mosaic" if calls else "dense", "losses": vals,
            "dense_first_loss": dense_loss,
            "cold_compile_s": round(ledger.compile_s, 1)}


def build_wrapper(model_kw: dict, seed: int = 0):
    """DalleWithVae over a random DALL·E and an untrained dVAE."""
    import jax
    from dalle_tpu.config import DVAEConfig
    from dalle_tpu.models.dalle import init_dalle
    from dalle_tpu.models.dvae import init_dvae
    from dalle_tpu.models.wrapper import (DalleWithVae, DiscreteVAEAdapter,
                                          dalle_config_for_vae)
    kw = dict(model_kw)
    fmap = kw.pop("image_fmap_size")
    layers = (kw["image_size"] // fmap).bit_length() - 1
    vae_cfg = DVAEConfig(image_size=kw.pop("image_size"),
                         num_tokens=kw.pop("image_vocab_size"),
                         num_layers=layers, codebook_dim=64, hidden_dim=32)
    vae = DiscreteVAEAdapter(*init_dvae(vae_cfg, jax.random.PRNGKey(seed + 1)))
    cfg = dalle_config_for_vae(vae, **kw)
    model, params = init_dalle(cfg, jax.random.PRNGKey(seed))
    return DalleWithVae(model, params, vae)


def tokenize_prompts(cfg):
    from dalle_tpu.text.tokenizer import SimpleTokenizer
    tok = SimpleTokenizer()
    say(f"[serve] tokenizer: vocab {tok.vocab_size}, BPE merge loop in "
        f"{'the native C++ core' if tok.bpe.uses_native_core else 'pure Python (g++ build failed)'}")
    texts = tok.tokenize(list(PROMPTS), context_length=cfg.text_seq_len,
                         truncate_text=True)
    # a model with a smaller text vocab than the tokenizer (CPU rehearsal)
    return texts % cfg.num_text_tokens


class SequentialPath:
    """generate_images_tokens on the ENGINE's params tree (verify skill,
    flow 6), one request under its own key — free-running, and
    teacher-forced on tokens the engine produced. Built once per serve
    phase: the dense and the paged engine share params tree, cache dtype
    and sampling knobs, so both compare against the same two programs."""

    def __init__(self, wrapper, engine):
        import jax
        import jax.numpy as jnp
        from dalle_tpu.models.dalle import DALLE
        from dalle_tpu.ops.sampling import top_k_filter
        model, self.params = wrapper.model, engine.params
        cache_dtype = engine.cache_dtype
        thres, temp = engine.filter_thres, max(engine.temperature, 1e-10)

        def forced(mdl, text, tokens, key):
            logits, cache, prefix_len = mdl._prefill(text, None, 1,
                                                     dtype=cache_dtype)

            def body(carry, xs):
                logits, cache = carry
                i, tok, sub = xs
                band = logits[:, mdl.num_text_tokens:].astype(jnp.float32)
                filt = top_k_filter(band, thres=thres)
                g = jax.random.gumbel(sub, band.shape, jnp.float32)
                score = filt / temp + g
                kth = jnp.min(jnp.where(filt == band, band, jnp.inf))
                gap = jnp.maximum(
                    jnp.max(score) - (band[0, tok] / temp + g[0, tok]),
                    (kth - band[0, tok]) / temp)
                new_logits, cache = mdl._decode_one(
                    tok[None], i, prefix_len + i, cache)
                return (new_logits, cache), (jnp.argmax(score), gap,
                                             jnp.std(band))

            # generate_images_tokens' key stream: a split chain, and the
            # last token under fold_in(key, n)
            n = tokens.shape[0]
            _, subs = jax.lax.scan(
                lambda k, _: tuple(jax.random.split(k)), key, None,
                length=n - 1)
            subs = jnp.concatenate([subs, jax.random.fold_in(key, n)[None]])
            _, out = jax.lax.scan(
                body, (logits, cache), (jnp.arange(n), tokens, subs))
            return out

        self._generate = jax.jit(lambda p, t, k: model.apply(
            p, t, k, cache_dtype=cache_dtype,
            method=DALLE.generate_images_tokens))
        self._forced = jax.jit(lambda p, t, toks, k: model.apply(
            p, t, toks, k, method=forced))

    def generate(self, text, seed):
        import jax
        import numpy as np
        return np.asarray(self._generate(self.params, text[None],
                                         jax.random.PRNGKey(seed))[0])

    def teacher_forced_gap(self, text, seed, tokens):
        """Feed ``tokens`` through the path's prefill + one cached decode
        step per token, under the request's key stream. At each position:
        the token the path samples, and how far the given token sits below
        the path's winner in perturbed-logit units (logit / temperature +
        gumbel; 0 where they agree) — or below its top-k threshold, if the
        path would have filtered it out. Returns (fraction of positions
        that agree, largest gap, mean std of the image-band logits)."""
        import jax
        import numpy as np
        ref, gaps, stds = self._forced(self.params, text[None],
                                       np.asarray(tokens, np.int32),
                                       jax.random.PRNGKey(seed))
        agree = np.asarray(ref) == np.asarray(tokens)
        gaps = np.where(agree, 0.0, np.asarray(gaps))
        return float(agree.mean()), float(gaps.max()), float(np.mean(stds))


def run_engine(engine, requests):
    """Submit (request_id, text, seed) triples, run to drain, return the
    completions by id and the wall seconds."""
    from dalle_tpu.serve import RequestQueue
    q = RequestQueue()
    for rid, text, seed in requests:
        q.submit(text, seed=seed, request_id=rid)
    q.close()
    t0 = time.perf_counter()
    done = engine.run(q)
    wall = time.perf_counter() - t0
    return {c.request_id: c for c in done}, wall


def check_completions(tag, wrapper, done, requests):
    """Every request completed, every token a valid image id, pixels finite."""
    import numpy as np
    cfg = wrapper.model.cfg
    require(sorted(done) == sorted(r[0] for r in requests), (
        f"{tag}: completed {sorted(done)} of {[r[0] for r in requests]}"))
    grids = np.stack([done[rid].tokens for rid, _, _ in requests])
    require(grids.shape == (len(requests), cfg.image_seq_len), grids.shape)
    require(grids.min() >= 0 and grids.max() < cfg.image_vocab_size, (
        f"{tag}: token outside [0, {cfg.image_vocab_size})"))
    pixels = np.asarray(wrapper.vae.decode(grids))
    size = cfg.image_size
    require(pixels.shape == (len(requests), size, size, 3), pixels.shape)
    require(np.isfinite(pixels).all(), f"{tag}: non-finite pixels")
    say(f"[{tag}] {len(requests)} requests completed; tokens in "
        f"[{grids.min()}, {grids.max()}]; vae.decode -> {pixels.shape} finite")


def compare_with_sequential(tag, path, done, requests):
    """Engine tokens vs generate_images_tokens for ``requests``; returns per
    request (first index of divergence or None = bitwise, teacher-forced
    agreement, largest teacher-forced gap)."""
    import numpy as np
    out = {}
    for rid, text, seed in requests:
        ref = path.generate(text, seed)
        diff = np.flatnonzero(ref != done[rid].tokens)
        agree, gap, std = path.teacher_forced_gap(text, seed,
                                                  done[rid].tokens)
        out[rid] = (int(diff[0]) if diff.size else None, agree, gap)
        say(f"[{tag}] request {rid} vs generate_images_tokens: "
            + ("bitwise equal" if not diff.size else
               f"{diff.size}/{ref.size} tokens differ, first at {diff[0]}")
            + f"; teacher-forced: {agree:.4f} of positions agree, largest "
            f"gap {gap:.4f} perturbed-logit units (logit std {std:.3f})")
    return out


def phase_serve(model_kw=FLAGSHIP, slots: int = 8, block_tokens: int = 64,
                on_chip: bool = True) -> dict:
    """Dense-KV serve engine, then its paged twin, on one wrapper."""
    from dalle_tpu.gateway.aot import step_lowering
    ledger = CompileLedger()
    t0 = time.perf_counter()
    wrapper = build_wrapper(model_kw)
    cfg = wrapper.model.cfg
    texts = tokenize_prompts(cfg)
    say(f"[serve] model + dVAE built in {time.perf_counter() - t0:.1f} s")

    # ---- phase 3: dense KV slabs, default precision (int8w + int8 KV) ----
    engine = wrapper.serve_engine(slots=slots)
    calls = mosaic_calls(step_lowering(engine).as_text())
    say(f"[serve] decode step attends through: "
        f"{'decode_attend_window_kernel (Mosaic x%d)' % calls if calls else 'dense XLA'}"
        f"; cache dtype {engine.cache_dtype.__name__}")
    if on_chip:
        require(calls > 0, "the served decode step holds no Mosaic call")
    # a dozen prompts over two runs of slots+3 requests each: a full refill
    # window, then three single-row refills as the first wave drains — the
    # first run is every program's first request, the second must compile
    # nothing
    n = slots + 3
    first = [(i, texts[i], 1000 + i) for i in range(n)]
    requests = [(100 + i, texts[i], 1000 + i)
                for i in range(len(texts) - n, len(texts))]
    done1, wall1 = run_engine(engine, first)
    check_completions("serve", wrapper, done1, first)
    warm = ledger.compiles
    done, wall = run_engine(engine, requests)
    steady = ledger.compiles - warm
    check_completions("serve", wrapper, done, requests)
    say(f"[serve] first {n} requests {wall1:.1f} s (compiles included); "
        f"next {n} in {wall:.2f} s = {wall / n:.3f} s/request, "
        f"{engine.stats.steps} decode steps, {engine.stats.refills} "
        f"refills, {steady} compiles; peak {peak_bytes() / 2**30:.2f} GiB")
    require(steady == 0, f"{steady} compiles after each program's first use")
    path = SequentialPath(wrapper, engine)
    dense_cmp = compare_with_sequential("serve", path, done, requests[:2])

    # ---- phase 4: paged twin, pool sized to the dense slabs' bytes ----
    paged = wrapper.serve_engine(slots=slots, kv_block_tokens=block_tokens)
    pcalls = mosaic_calls(step_lowering(paged).as_text())
    say(f"[serve_paged] decode step attends through: "
        f"{'page gather + decode_attend_window_kernel (Mosaic x%d)' % pcalls if pcalls else 'page gather + dense XLA'}"
        f"; {paged.kv_pool_blocks} blocks x {block_tokens} tokens")
    if on_chip:
        require(pcalls > 0, "the paged decode step holds no Mosaic call")
    # five requests, two of them repeating an earlier prompt under a new
    # seed; run twice (misses, hits and COW forks all warm in the first)
    preqs = [(200 + i, texts[i], 1000 + i) for i in range(3)]
    preqs += [(203, texts[0], 5000), (204, texts[1], 5001)]
    run_engine(paged, [(r - 100, t, s) for r, t, s in preqs])
    warm = ledger.compiles
    pdone, pwall = run_engine(paged, preqs)
    psteady = ledger.compiles - warm
    check_completions("serve_paged", wrapper, pdone, preqs)
    kv = paged.kv_stats()
    say(f"[serve_paged] {len(preqs)} requests in {pwall:.2f} s, {psteady} "
        f"compiles; kv_stats: radix_full_hits={kv['radix_full_hits']} "
        f"prefix_hit_tokens={kv['prefix_hit_tokens']} "
        f"cow_copies={kv['cow_copies']} pages_used={kv['pages_used']}")
    require(psteady == 0, f"{psteady} compiles after warm-up (paged)")
    require(kv["radix_full_hits"] >= 2, kv)
    paged_cmp = compare_with_sequential("serve_paged", path, pdone,
                                        [preqs[0], preqs[3]])
    say("[serve] " + ledger.line())

    cmps = (*dense_cmp.values(), *paged_cmp.values())
    bitwise = all(first is None for first, _, _ in cmps)
    require(all(agree >= TEACHER_FORCED_MIN_AGREE
                and gap <= TEACHER_FORCED_MAX_GAP for _, agree, gap in cmps),
            f"engine tokens off the sequential path (agreement < "
            f"{TEACHER_FORCED_MIN_AGREE} or gap > {TEACHER_FORCED_MAX_GAP}): "
            f"dense {dense_cmp}, paged {paged_cmp}")
    return {"tier": "mosaic" if calls else "dense",
            "paged_tier": "mosaic" if pcalls else "dense",
            "bitwise_vs_sequential": bitwise,
            "cold_compile_s": round(ledger.compile_s, 1)}


def _multichip_first_loss(mesh_kw: dict, n_devices: int, steps: int,
                          model_kw=FLAGSHIP, batch: int = 8) -> dict:
    import jax
    import numpy as np
    from dalle_tpu.config import MeshConfig
    ledger = CompileLedger()
    devices = jax.devices()[:n_devices]
    cfg, trainer = make_trainer(model_kw, batch=batch, name="multichip",
                                optimizer="adafactor",
                                mesh_cfg=MeshConfig(**mesh_kw),
                                devices=devices)
    tag = f"multichip {dict(trainer.mesh.shape)}"
    # where the parameter shards actually live: code that has only seen
    # virtual devices may put everything on the first
    leaves = jax.tree.leaves(trainer.state.params)
    param_bytes = {d.id: 0 for d in devices}
    for leaf in leaves:
        for s in leaf.addressable_shards:
            param_bytes[s.device.id] += s.data.nbytes
    split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    shard_devs = sorted(d for d, b in param_bytes.items() if b)
    say(f"[{tag}] {split} of {len(leaves)} param leaves split across "
        f"devices; param bytes held per device: "
        + ", ".join(f"{d}: {b / 2**30:.2f} GiB"
                    for d, b in param_bytes.items()))
    text, ids = synthetic_batch(cfg, batch, seed=0)
    losses = []
    t0 = time.perf_counter()
    for _ in range(steps):
        losses.append(float(trainer.train_step(text, ids)["loss"]))
    jax.block_until_ready(trainer.state.params)
    say(f"[{tag}] {steps} steps in {time.perf_counter() - t0:.1f} s "
        f"(first includes the compile); losses "
        + ", ".join(f"{v:.6f}" for v in losses))
    for d in devices:
        st = d.memory_stats() or {}
        say(f"[{tag}] device {d.id}: bytes_in_use "
            f"{st.get('bytes_in_use', 0) / 2**30:.2f} GiB, peak "
            f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
    say(f"[{tag}] " + ledger.line())
    require(all(np.isfinite(losses)), losses)
    require(len(shard_devs) == n_devices, (
        f"parameter shards live on devices {shard_devs}, expected "
        f"{n_devices} distinct"))
    return {"first_loss": losses[0], "losses": losses,
            "shard_devices": shard_devs}


def phase_multichip_sharded() -> dict:
    """1.4B train step on the four-chip mesh _factor_mesh(4) picks."""
    out = _multichip_first_loss(dict(dp=1, fsdp=2, tp=2, sp=1), 4, steps=3)
    require(out["losses"][-1] < out["losses"][0], out["losses"])
    return out


def phase_multichip_single() -> dict:
    """The comparison: same batch and seed on a one-device mesh."""
    return _multichip_first_loss(dict(dp=1, fsdp=1, tp=1, sp=1), 1, steps=1)


def child_main(phase: str, want_devices: int) -> None:
    device = require_tpu()
    if device["count"] != want_devices:
        sys.exit(f"chip_smoke.py phase {phase} wants {want_devices} TPU "
                 f"chip(s) and jax found {device['count']}.")
    from dalle_tpu.train.metrics import device_peak_tflops
    from dalle_tpu.utils.misc import enable_compilation_cache
    enable_compilation_cache()
    os.makedirs(OUT, exist_ok=True)
    say(f"[{phase}] device: {device['platform']} {device['kind']!r} x "
        f"{device['count']} (peak table row: {device_peak_tflops()} "
        f"TFLOP/s bf16)")
    t0 = time.perf_counter()
    result = globals()[f"phase_{phase}"]()
    result.update(phase=phase, ok=True, device=device,
                  seconds=round(time.perf_counter() - t0, 1))
    say(json.dumps(result))


# --------------------------------------------------------------------------
# parent: no jax in this process
# --------------------------------------------------------------------------

def run_child(phase: str, want_devices: int) -> dict:
    """Run one phase as a child, echo its output, return its last JSON
    line. The child is killed if it outlives PHASE_TIMEOUT_S or if this
    process is interrupted."""
    cmd = [sys.executable, "-c", "import chip_smoke; "
           f"chip_smoke.child_main({phase!r}, {want_devices})"]
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE, text=True)
    killer = threading.Timer(PHASE_TIMEOUT_S, proc.kill)
    killer.start()
    last = ""
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.strip():
                last = line
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        sys.exit(f"chip_smoke.py: phase {phase} failed (exit code {rc}).")
    result = json.loads(last)
    require(result.get("ok") is True and result["phase"] == phase, result)
    return result


def parent_main(multichip: bool) -> None:
    require("jax" not in sys.modules, "the parent must stay off jax")
    t0 = time.perf_counter()
    phases = MULTICHIP_PHASES if multichip else PHASES
    want = 4 if multichip else 1
    results = [run_child(p, want) for p in phases]
    devices = [r["device"] for r in results]
    require(all(d == devices[0] for d in devices), devices)
    if multichip:
        sharded, single = (r["first_loss"] for r in results)
        rel = abs(sharded - single) / abs(single)
        print(f"[multichip] first-step loss: fsdp=2 x tp=2 {sharded:.6f} vs "
              f"one device {single:.6f} (rel diff {rel:.2e}, rtol 2e-4)",
              flush=True)
        # the tolerance __graft_entry__.dryrun_multichip holds the same
        # comparison to
        require(rel <= 2e-4, (sharded, single))
    print(f"[chip_smoke] {len(results)} phases ok in "
          f"{time.perf_counter() - t0:.0f} s: "
          + "; ".join(f"{r['phase']} {r['seconds']} s" for r in results),
          flush=True)
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run the four-chip sharded train step and its "
                         "one-device comparison, and no other phase")
    parent_main(ap.parse_args().multichip)


if __name__ == "__main__":
    main()

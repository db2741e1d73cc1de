"""Shared CLI plumbing: VAE reconstitution and checkpoint-params loading.

Reference: legacy/train_dalle.py:249-299 — the VAE precedence chain
(resume-embedded params > ``--vae_path`` trained dVAE > ``--taming`` VQGAN >
OpenAI pretrained) — and legacy/generate.py:82-106 (rebuild exact model from
checkpoint-embedded hparams + vae_class_name).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_model_checkpoint(ckpt_dir: str, expect_class: str, config_cls,
                          init_fn):
    """Generic checkpoint reconstitution from embedded metadata (reference
    legacy/generate.py:82-106): validate model_class, rebuild the model from
    ``hparams``, restore params. Returns (model, params, meta)."""
    import jax
    from dalle_tpu.config import OptimConfig
    from dalle_tpu.train.checkpoints import CheckpointManager
    from dalle_tpu.train.train_state import TrainState, make_optimizer

    mgr = CheckpointManager(ckpt_dir)
    meta = mgr.load_metadata()
    if meta is None or meta.get("model_class") != expect_class:
        raise ValueError(f"{ckpt_dir} is not a {expect_class} checkpoint "
                         f"(model_class={meta and meta.get('model_class')})")
    cfg = config_cls.from_dict(meta["hparams"])
    optim = OptimConfig.from_dict(meta.get("train", {}).get("optim", {})) \
        if meta.get("train") else OptimConfig()
    model, params = init_fn(cfg, jax.random.PRNGKey(0))
    template = TrainState.create(apply_fn=model.apply, params=params,
                                 tx=make_optimizer(optim))
    state, _ = mgr.restore(template)
    mgr.close()
    return model, state.params, meta


def save_vae_sidecar(output_dir: str, vae):
    """Embed the (frozen) VAE weights+hparams inside the DALL·E checkpoint
    directory, so generation needs only ``--dalle_path`` — the reference's
    checkpoints carry the vae as a submodule of the DALLE state dict plus
    ``vae_params``/``vae_class_name`` (legacy/train_dalle.py:535-582).
    Pretrained wrappers (OpenAI/VQGAN) are skipped: they rebuild from their
    own cached artifacts, exactly like the reference (generate.py:93-100)."""
    from dalle_tpu.models.wrapper import DiscreteVAEAdapter
    if type(vae) is not DiscreteVAEAdapter:
        return
    from dalle_tpu.train.checkpoints import CheckpointManager
    mgr = CheckpointManager(os.path.join(output_dir, "vae"))
    mgr.save(0, vae.params, {"vae_class_name": type(vae).__name__,
                             "hparams": vae.model.cfg.to_dict()})
    mgr.close()


def load_vae_sidecar(ckpt_dir: str):
    """Rebuild the VAE embedded by ``save_vae_sidecar``; None if absent."""
    vdir = os.path.join(ckpt_dir, "vae")
    if not os.path.isdir(vdir):
        return None
    import jax
    from dalle_tpu.config import DVAEConfig
    from dalle_tpu.models.dvae import init_dvae
    from dalle_tpu.models.wrapper import DiscreteVAEAdapter
    from dalle_tpu.train.checkpoints import CheckpointManager

    mgr = CheckpointManager(vdir)
    meta = mgr.load_metadata()
    if meta is None or meta.get("vae_class_name") != "DiscreteVAEAdapter":
        mgr.close()
        return None
    cfg = DVAEConfig.from_dict(meta["hparams"])
    model, template = init_dvae(cfg, jax.random.PRNGKey(0))
    params, _ = mgr.restore(template)
    mgr.close()
    return DiscreteVAEAdapter(model, params)


def load_dvae_adapter(ckpt_dir: str):
    """Restore a scripts/train_vae.py checkpoint into a DiscreteVAEAdapter."""
    from dalle_tpu.config import DVAEConfig
    from dalle_tpu.models.dvae import init_dvae
    from dalle_tpu.models.wrapper import DiscreteVAEAdapter

    model, params, _ = load_model_checkpoint(ckpt_dir, "DiscreteVAE",
                                             DVAEConfig, init_dvae)
    return DiscreteVAEAdapter(model, params)


def build_vae_from_args(args, backend=None):
    """The reference's VAE precedence chain for CLIs (train_dalle.py:264-299).
    Returns a VAEAdapter."""
    if getattr(args, "vae_path", None):
        return load_dvae_adapter(args.vae_path)
    if getattr(args, "taming", False) or getattr(args, "vqgan_model_path", None):
        from dalle_tpu.models.pretrained import VQGanVAE
        return VQGanVAE.from_pretrained(
            vqgan_model_path=getattr(args, "vqgan_model_path", None),
            vqgan_config_path=getattr(args, "vqgan_config_path", None),
            backend=backend)
    if getattr(args, "untrained_vae", False):
        # smoke-test path: random dVAE, no pretrained weights needed
        import jax
        from dalle_tpu.config import DVAEConfig
        from dalle_tpu.models.dvae import init_dvae
        from dalle_tpu.models.wrapper import DiscreteVAEAdapter
        cfg = DVAEConfig(image_size=args.image_size,
                         num_tokens=getattr(args, "untrained_vae_tokens", 512),
                         codebook_dim=64,
                         num_layers=getattr(args, "untrained_vae_layers", 2),
                         hidden_dim=32)
        model, params = init_dvae(cfg, jax.random.PRNGKey(0))
        return DiscreteVAEAdapter(model, params)
    from dalle_tpu.models.pretrained import OpenAIDiscreteVAE
    return OpenAIDiscreteVAE.from_pretrained(backend=backend)


def add_vae_args(parser):
    grp = parser.add_argument_group("vae")
    grp.add_argument("--vae_path", type=str, default=None,
                     help="checkpoint dir from scripts/train_vae.py")
    grp.add_argument("--taming", action="store_true",
                     help="use the pretrained taming VQGAN")
    grp.add_argument("--vqgan_model_path", type=str, default=None)
    grp.add_argument("--vqgan_config_path", type=str, default=None)
    grp.add_argument("--untrained_vae", action="store_true",
                     help="random dVAE (smoke tests; no download needed)")
    grp.add_argument("--untrained_vae_tokens", type=int, default=512)
    grp.add_argument("--untrained_vae_layers", type=int, default=2)
    return parser


def save_image_grid(images, path):
    """images (b, H, W, C) float [0,1] → one PNG per row dir-less save."""
    import numpy as np
    from PIL import Image
    arr = (np.asarray(images) * 255).clip(0, 255).astype("uint8")
    for i, im in enumerate(arr):
        Image.fromarray(im).save(path.format(i))


def add_compile_cache_args(parser):
    """Persistent XLA compilation cache flags, shared by every CLI (train
    AND serve): a rejoining worker or a scaled-up serving replica reads
    compiled programs back from disk instead of repaying XLA (the
    trace is still paid — gateway AOT bundles skip that too, see
    docs/SERVING.md)."""
    grp = parser.add_argument_group("compilation cache (docs/SERVING.md)")
    grp.add_argument("--compile_cache_dir", type=str, default=None,
                     help="persistent XLA compilation cache directory "
                          "(content-addressed; safe to share across "
                          "processes and runs). Default: .xla_cache/ in "
                          "the checkout. JAX_COMPILATION_CACHE_DIR, when "
                          "set, wins over this flag")
    grp.add_argument("--no_compile_cache", action="store_true",
                     help="disable the persistent compilation cache "
                          "(every process recompiles from scratch)")
    return parser


def enable_compile_cache(args) -> bool:
    """Apply add_compile_cache_args flags. Call BEFORE the first jit
    dispatch — programs compiled earlier in the process are not
    retro-cached. Returns True when the cache was enabled."""
    if getattr(args, "no_compile_cache", False):
        return False
    from dalle_tpu.utils.misc import enable_compilation_cache
    enable_compilation_cache(args.compile_cache_dir)
    return True


def add_profiler_args(parser):
    """On-demand ``jax.profiler`` capture, shared by train AND serve CLIs:
    ``kill -USR2 <pid>`` records a bounded trace into the artifacts dir —
    the "the p99 is weird RIGHT NOW" tool, with zero cost until the signal
    arrives and a hard stop after ``--profiler_capture_s`` so a forgotten
    capture can't fill the disk."""
    grp = parser.add_argument_group("on-demand profiler "
                                    "(docs/OBSERVABILITY.md)")
    grp.add_argument("--profiler_dir", type=str, default=None,
                     help="SIGUSR2 target dir for bounded jax.profiler "
                          "traces (default: <output/artifacts dir>/profile;"
                          " 'off' disables the handler)")
    grp.add_argument("--profiler_capture_s", type=float, default=5.0,
                     help="seconds per capture (the bound)")
    return parser


def install_sigusr2_profiler(default_dir: str, args=None) -> bool:
    """Install the SIGUSR2 handler (main thread only — call from the CLI's
    main). Each signal starts one ``jax.profiler`` trace into a timestamped
    subdir and a daemon timer stops it after the bound; a signal landing
    mid-capture is ignored (one capture at a time). Returns False when
    disabled or uninstallable."""
    import signal
    import threading
    import time

    outdir = default_dir
    capture_s = 5.0
    if args is not None:
        if getattr(args, "profiler_dir", None) == "off":
            return False
        outdir = getattr(args, "profiler_dir", None) or default_dir
        capture_s = float(getattr(args, "profiler_capture_s", 5.0))
    state = {"active": False, "path": None}

    def _stop():
        import jax
        try:
            jax.profiler.stop_trace()
            # the step's instruction -> [layer, phase, op_name] table beside
            # the capture (docs/OBSERVABILITY.md "Device time by scope")
            from dalle_tpu.obs.device import write_program_scopes
            write_program_scopes(
                os.path.join(state["path"], "program_scopes.json"))
        except Exception as exc:  # noqa: BLE001 - a failed stop must not
            # kill the timer thread; the next capture starts a fresh trace
            print(f"[graftscope] profiler stop failed: {exc!r}")
        state["active"] = False

    def _handler(_sig, _frame):
        if state["active"]:
            return
        state["active"] = True
        import jax
        path = os.path.join(outdir, time.strftime("profile_%Y%m%d_%H%M%S"))
        state["path"] = path
        os.makedirs(path, exist_ok=True)
        try:
            jax.profiler.start_trace(path)
        except Exception as exc:  # noqa: BLE001 - an already-running or
            # unsupported profiler must not kill the training/serving loop
            # the signal interrupted
            print(f"[graftscope] profiler start failed: {exc!r}")
            state["active"] = False
            return
        print(f"[graftscope] SIGUSR2: profiling {capture_s:.1f}s → {path}",
              flush=True)
        threading.Timer(capture_s, _stop).start()

    try:
        signal.signal(signal.SIGUSR2, _handler)
    except (ValueError, AttributeError):   # non-main thread / platform
        return False
    return True


def add_health_args(parser):
    """graftpulse model-health flags shared by every train CLI
    (docs/OBSERVABILITY.md "Model health"): the in-jit taps + anomaly
    sentries. Off by default — enabling changes the compiled step program
    (pinned by the graftir goldens, which build health-on)."""
    grp = parser.add_argument_group("model health (graftpulse, "
                                    "docs/OBSERVABILITY.md)")
    grp.add_argument("--health", action="store_true",
                     help="fuse per-layer-group grad/param/update/"
                          "non-finite taps (and codebook vitals on the VAE "
                          "trainers) into the jitted step and run the "
                          "anomaly sentries — zero added host syncs; "
                          "breaches fire dalle_health_* gauges, flight "
                          "bundles and the obs_report MODEL-HEALTH verdict")
    grp.add_argument("--health_group_depth", type=int, default=1,
                     help="pytree depth for layer groups (1 = model "
                          "subtrees)")
    grp.add_argument("--health_loss_z", type=float, default=6.0,
                     help="loss-spike z-score threshold")
    grp.add_argument("--health_grad_factor", type=float, default=10.0,
                     help="grad-norm explosion factor over the EMA")
    grp.add_argument("--health_perplexity_floor", type=float, default=4.0,
                     help="codebook-collapse floor (usage perplexity)")
    grp.add_argument("--health_flight_dir", type=str, default=None,
                     help="configure a flight recorder here so health "
                          "breaches dump post-mortem bundles (default: "
                          "<output_dir>/health_bundles when --health)")
    return parser


def health_obs_kwargs(args) -> dict:
    """ObsConfig kwargs from add_health_args flags."""
    return {
        "health": args.health,
        "health_group_depth": args.health_group_depth,
        "health_loss_z": args.health_loss_z,
        "health_grad_factor": args.health_grad_factor,
        "health_perplexity_floor": args.health_perplexity_floor,
    }


def install_health_recorder(args, default_dir: str) -> bool:
    """With --health, make sure a flight recorder exists so breach bundles
    have somewhere to land (an already-configured recorder wins). Returns
    True when a recorder was installed here."""
    if not getattr(args, "health", False):
        return False
    from dalle_tpu import obs
    if obs.get_recorder() is not None:
        return False
    obs.configure_recorder(getattr(args, "health_flight_dir", None)
                           or default_dir)
    return True


def add_resilience_args(parser):
    """graftmend flags shared by every train CLI (docs/RESILIENCE.md):
    the SIGTERM graceful-preemption handler (default ON — the k8s/TPU
    preemption contract) and the breach→action automation over the
    graftpulse sentries (opt-in; needs --health for the detectors to see
    anything)."""
    grp = parser.add_argument_group("resilience (graftmend, "
                                    "docs/RESILIENCE.md)")
    grp.add_argument("--no_preemption_handler", action="store_true",
                     help="do NOT install the SIGTERM handler (default: "
                          "SIGTERM finishes the in-flight step, takes a "
                          "synchronous drained save, and exits 0)")
    grp.add_argument("--breach_actions", action="store_true",
                     help="act on graftpulse breaches: nan-precursor → "
                          "preemptive snapshot, grad-explosion → rollback "
                          "+ lr cut, codebook-collapse → lr cut + gumbel "
                          "re-anneal (pair with --health)")
    grp.add_argument("--lr_cut_factor", type=float, default=0.5,
                     help="lr_scale multiplier applied per lr-cut action")
    return parser


def install_resilience(args, trainer, log=print):
    """Arm the graftmend layers on a built trainer per the CLI flags."""
    if not getattr(args, "no_preemption_handler", False):
        trainer.install_preemption_handler(log=log)
    if getattr(args, "breach_actions", False):
        from dalle_tpu.train.actions import BreachActions
        BreachActions(trainer, lr_cut_factor=args.lr_cut_factor,
                      log=log).attach()
        if not getattr(args, "health", False):
            log("[actions] --breach_actions without --health: the "
                "detectors see no health/* columns and will never fire")


def add_overlap_args(parser):
    """Host-overlap flags shared by every train CLI (docs/PERFORMANCE.md):
    async checkpointing, device prefetch depth, and the rollback-snapshot
    placement."""
    grp = parser.add_argument_group("host overlap (docs/PERFORMANCE.md)")
    grp.add_argument("--sync_checkpointing", action="store_true",
                     help="disable async orbax saves (save() blocks until "
                          "the checkpoint is durable, the pre-PR3 behavior)")
    grp.add_argument("--device_prefetch", type=int, default=2,
                     help="batches kept device-resident ahead of the step "
                          "loop (0 disables; H2D then rides the critical "
                          "path)")
    grp.add_argument("--rollback_snapshot", type=str, default="auto",
                     choices=["auto", "device", "host"],
                     help="where the NaN-rollback snapshot lives (auto = "
                          "device when HBM headroom allows)")
    return parser


def overlap_train_kwargs(args) -> dict:
    """TrainConfig kwargs from add_overlap_args flags."""
    return {
        "async_checkpointing": not args.sync_checkpointing,
        "device_prefetch": args.device_prefetch,
        "rollback_snapshot": args.rollback_snapshot,
    }

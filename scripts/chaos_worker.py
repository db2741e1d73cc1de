#!/usr/bin/env python
"""One member of a graftmend chaos/elastic pod (docs/RESILIENCE.md).

Spawned by ``scripts/chaos_smoke.py``'s :class:`ElasticAgent` (or run by
hand for debugging): installs the chaos FaultPlan from the env, joins the
pod's current membership epoch over the real gloo/DCN path
(``jax.distributed.initialize`` through the retried backend connect),
trains a tiny dVAE with deterministic per-step synthetic batches, heartbeats
every step, restores from the last durable checkpoint on (re)start, and on
completion writes a digest artifact — the sha256 over the raw bytes of
every (params, opt_state) leaf — that the smoke compares BITWISE against an
uninterrupted reference run at the same step.

Exit protocol (what the agent keys on):
  * 0  — reached the target step; digest written.
  * 77 (``EXIT_RECONFIGURE``) — preempted (SIGTERM graceful save landed)
    or a peer died: respawn me into the next epoch.
  * anything else — crash (the agent reconfigures per policy).

Determinism contract: the batch for host step s is
``RandomState(seed + s)``, and every rng draw in the trainer folds off the
host step — so re-executing [restore-step, crash-step] after recovery
reproduces the exact bits of a run that never crashed.
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_batch(seed: int, step: int, batch: int, size: int):
    import numpy as np
    rng = np.random.RandomState(seed + step)
    return (rng.rand(batch, size, size, 3).astype(np.float32),)


def state_digest(state) -> str:
    """sha256 over every (params, opt_state) leaf's raw bytes, in
    deterministic tree order — the bitwise-resume oracle."""
    import jax
    import numpy as np
    h = hashlib.sha256()
    for leaf in jax.tree.leaves((state.params, state.opt_state)):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--run_dir", required=True,
                    help="shared pod dir (epoch file, heartbeats, ckpt)")
    ap.add_argument("--target_steps", type=int, default=8)
    ap.add_argument("--save_every", type=int, default=2,
                    help="0 = never save (reference legs)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--restore_step", type=int, default=None,
                    help="pin the restore step (reference legs); default: "
                    "resume from latest durable if any")
    ap.add_argument("--reference", action="store_true",
                    help="reference leg: no elastic runtime, no heartbeats")
    ap.add_argument("--peer_timeout_s", type=float, default=0.0)
    ap.add_argument("--health_page", action="store_true",
                    help="arm the graftpulse health taps + sentry and wire "
                    "breaches into the heartbeat page marker "
                    "(degrade.install_breach_pager) — the agent's "
                    "DegradeMonitor then drains this worker on a breach")
    ap.add_argument("--sync_ckpt", action="store_true",
                    help="synchronous checkpointing: every save is durable "
                    "at its boundary (scenarios that script against the "
                    "newest-durable-step need this determinism; the default "
                    "async path is the production config)")
    ap.add_argument("--compile_cache", default="",
                    help="persistent XLA compile cache dir (shared across "
                    "the pod; makes a rejoin near-zero-compile). "
                    "JAX_COMPILATION_CACHE_DIR, when set, wins over it")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from dalle_tpu import chaos, obs
    obs.configure()
    chaos.install_from_env()

    from dalle_tpu.config import (AnnealConfig, DVAEConfig, MeshConfig,
                                  ObsConfig, TrainConfig)
    from dalle_tpu.parallel import backend as B
    from dalle_tpu.parallel import elastic
    from dalle_tpu.train.trainer_vae import VAETrainer
    from dalle_tpu.utils.misc import enable_compilation_cache

    if args.compile_cache:
        enable_compilation_cache(args.compile_cache)

    worker = None
    if not args.reference and elastic.DIR_ENV in os.environ:
        worker = elastic.ElasticWorker.from_env(
            hb_interval_s=0.1, peer_timeout_s=args.peer_timeout_s)
        # start NOW: the beater covers the long no-step phases (backend
        # dial-in, restore, first-step compile) so liveness readers see a
        # fresh-but-not-yet-stepping worker, not a corpse
        worker.start()
        ep = worker.epoch
        pid = ep.process_id(worker.worker_id)
        if pid is None:
            print(f"worker {worker.worker_id}: not a member of epoch "
                  f"{ep.epoch}; exiting")
            return 0
        ns = argparse.Namespace(
            distributed_backend="jax",
            coordinator_address=ep.coordinator_address if ep.nproc > 1
            else None,
            num_processes=ep.nproc if ep.nproc > 1 else None,
            process_id=pid)
    else:
        ns = argparse.Namespace(distributed_backend="jax",
                                coordinator_address=None,
                                num_processes=None, process_id=None)
    backend = B.set_backend_from_args(ns).initialize(MeshConfig())

    model_cfg = DVAEConfig(image_size=16, num_tokens=16, codebook_dim=8,
                           num_layers=1, num_resnet_blocks=0, hidden_dim=8)
    tc = TrainConfig(
        batch_size=args.batch, seed=args.seed, log_every=1,
        save_every_steps=args.save_every or 0,
        keep_n_checkpoints=None,           # fallback needs older steps
        checkpoint_dir=os.path.join(args.run_dir, "ckpt"),
        preflight_checkpoint=False,
        async_checkpointing=not args.sync_ckpt,
        device_prefetch=0,                 # resume math owns the iterator
        obs=ObsConfig(health=True) if args.health_page else ObsConfig(),
        mesh=MeshConfig())
    trainer = VAETrainer(model_cfg, tc, anneal_cfg=AnnealConfig(),
                         backend=backend)
    if worker is not None and args.health_page:
        # graftward drain-on-health-page: build the sentry PRE-fit (the
        # BreachActions.attach precedent — fit's is-None check then reuses
        # it) and chain its on_breach into the heartbeat page marker
        from dalle_tpu.degrade import install_breach_pager
        from dalle_tpu.obs.anomaly import HealthSentry
        if trainer.health_sentry is None:
            trainer.health_sentry = HealthSentry.from_obs_config(tc.obs)
        install_breach_pager(worker, trainer.health_sentry)

    restored_from = None
    if args.restore_step is not None:
        trainer.restore(args.restore_step)
        restored_from = args.restore_step
    elif trainer.ckpt.latest_step() is not None:
        trainer.restore()
        restored_from = int(trainer._host_step)
    print(f"worker: world={backend.get_world_size()} "
          f"proc={os.getpid()} start_step={trainer._host_step} "
          f"restored_from={restored_from}")

    def leave_pod():
        """Exit discipline: BARRIER, then detach from the coordination
        service. Without this, the first worker to exit kills its peers —
        the coordination service declares it dead and fatally terminates
        every other member, and a peer mid-collective can even read
        garbage instead of erroring. Symmetric exits (everyone done, or
        everyone preempted at the same boundary) meet at the barrier;
        asymmetric deaths are the agent's job, not ours."""
        try:
            backend.local_barrier()
            import jax
            if backend.get_world_size() > 1:
                jax.distributed.shutdown()
        except Exception as exc:  # noqa: BLE001 - a broken pod (peer died
            # while we drained) cannot barrier; the agent handles it
            print(f"worker: leave_pod best-effort failed: {exc!r}")

    trainer.install_preemption_handler()

    batches = (make_batch(args.seed, s, args.batch, model_cfg.image_size)
               for s in range(trainer._host_step, args.target_steps))
    on_step = writer = None
    if worker is not None:
        # graftward straggler signal: forward the grafttrace step
        # breakdown's device/collective wait (t_dispatch + t_sync) into
        # the heartbeat — in lockstep SPMD the worker that never waits IS
        # the straggler (degrade/detector.py). The writer sees step s's
        # record after on_step(s) fired, so beats carry the previous
        # step's wait; one step stale, which the detector's EWMA absorbs.
        last_m: dict = {}

        class _HBWriter:
            def log(self, step, m):
                last_m.clear()
                last_m.update(m)
        writer = _HBWriter()

        def on_step(step):
            blocked = (last_m.get("t_dispatch_s", 0.0)
                       + last_m.get("t_sync_s", 0.0)
                       if "t_dispatch_s" in last_m else None)
            worker.on_step(step, blocked_s=blocked)
    trainer.fit(batches, steps=args.target_steps,
                metrics_writer=writer, on_step=on_step)
    if worker is not None:
        worker.stop()

    if trainer.preempted and trainer._host_step < args.target_steps:
        # graceful preemption before the budget: state is durable — ask
        # the agent to respawn us into the next epoch. Real preemption
        # SIGTERMs every host at once, so the whole gang passes through
        # here together and the exit barrier is symmetric.
        print(f"worker: preempted at step {trainer._host_step}; requesting "
              "reconfiguration")
        leave_pod()
        return elastic.EXIT_RECONFIGURE

    snap = obs.metrics_snapshot()
    artifact = {
        "worker_id": worker.worker_id if worker is not None else -1,
        "epoch": worker.epoch.epoch if worker is not None else -1,
        "step": int(trainer._host_step),
        "world_size": int(backend.get_world_size()),
        "restored_from": restored_from,
        "digest": state_digest(trainer.state),
        "counters": {k: v for k, v in snap.items()
                     if k.startswith(("retry.", "chaos.", "ckpt.",
                                      "elastic."))},
    }
    tag = (f"w{artifact['worker_id']}" if worker is not None else "ref")
    out = os.path.join(args.run_dir, f"digest_{tag}.json")
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(artifact, fh, indent=2)
    print(f"worker: done at step {artifact['step']} "
          f"digest={artifact['digest'][:16]}… → {out}")
    leave_pod()
    return 0


if __name__ == "__main__":
    sys.exit(main())

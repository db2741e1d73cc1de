"""dVAE trainer — one jitted SPMD train step + host-side epoch loop.

Reference call stack: legacy/train_vae.py (§3.4 of SURVEY.md) — epoch loop with
Gumbel temperature annealing ``temp = max(temp·exp(−rate·step), temp_min)``
(:269-271), codebook-index histogram as a collapse monitor (:245-264), loss
averaging over workers, checkpoint {hparams, weights}. The fork adds NaN
rollback (vae.py:100-110).

TPU design: the entire step (loss, grads, psum over dp via shardings, optimizer)
is ONE jitted function with the state donated (params update in place in HBM);
temperature enters as a traced scalar so annealing doesn't retrigger
compilation; the gumbel rng is folded from the step counter for cross-host
determinism.
"""

from __future__ import annotations

import math
import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import AnnealConfig, DVAEConfig, TrainConfig
from ..models.dvae import DiscreteVAE, init_dvae
from ..obs import span
from .base_trainer import BaseTrainer
from .metrics import ThroughputMeter, count_params
from .train_state import (TrainState, cast_floating, compute_dtype,
                          jit_step)


def anneal_temperature(cfg: AnnealConfig, global_step: int) -> float:
    return max(cfg.starting_temp * math.exp(-cfg.anneal_rate * global_step),
               cfg.temp_min)


@functools.lru_cache(maxsize=64)
def _vae_step_body(model: DiscreteVAE, dtype=None, health: bool = False,
                   health_depth: int = 1):
    # memoized on (model-config, dtype, health wiring) so equal-config
    # trainers hand jit_step the SAME body object and share one jitted
    # wrapper. ``health`` fuses the graftpulse taps (obs/health.py) into the
    # program: the dVAE's codebook/gumbel vitals ride the loss aux, the
    # per-layer-group grad/param/update stats reduce in the same step — all
    # scalars in the metrics dict, zero added host syncs.
    def loss_fn(params, images, key, temp):
        if dtype is not None:
            images = images.astype(dtype)
        with jax.named_scope("forward"):
            out = model.apply(
                cast_floating(params, dtype), images, temp=temp,
                return_loss=True, return_recons=True, return_health=health,
                rngs={"gumbel": key})
        if health:
            loss, _recons, hm = out
            return loss, hm
        loss, _recons = out
        return loss, None

    def step(state: TrainState, images, key, temp):
        (loss, hm), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, images, key, temp)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
        if health:
            from ..obs.health import tree_health
            state, updates = state.apply_gradients(grads, value=loss,
                                                   return_updates=True)
            metrics.update(hm)
            metrics.update(tree_health(grads, state.params, updates,
                                       depth=health_depth))
        else:
            state = state.apply_gradients(grads, value=loss)
        return state, metrics

    return step


def make_vae_train_step(model: DiscreteVAE, dtype=None, state=None,
                        health: bool = False, health_depth: int = 1):
    """Returns step(state, images, key, temp) -> (state, metrics). jit-once
    (the (body, shardings)-memoized train_state.jit_step); the state is
    donated so params/moments update in place in HBM. ``state`` pins the
    output state's shardings to the input's — see jit_step. ``dtype``
    selects the compute precision (params cast per-step; masters stay f32);
    ``health`` fuses the graftpulse model-health taps into the program
    (docs/OBSERVABILITY.md)."""
    return jit_step(_vae_step_body(model, dtype, health, health_depth), state)


@functools.lru_cache(maxsize=64)
def make_vae_train_multi_step(model: DiscreteVAE, dtype=None,
                              health: bool = False, health_depth: int = 1):
    """k steps per dispatch (train_state.make_scanned_steps) over stacked
    (images, keys, temps) — the identical step body, so with matching key and
    temperature streams the result equals k single dispatches."""
    from .train_state import make_scanned_steps
    return make_scanned_steps(_vae_step_body(model, dtype, health,
                                             health_depth))


@partial(jax.jit, static_argnums=1)
def _codebook_counts(indices, num_tokens):
    """Histogram of codebook usage — the collapse monitor the reference logs to
    wandb (legacy/train_vae.py:258-264)."""
    return jnp.bincount(indices.reshape(-1), length=num_tokens)


class VAETrainer(BaseTrainer):
    model_class = "DiscreteVAE"

    @span("trainer/init")
    def __init__(self, model_cfg: DVAEConfig, train_cfg: TrainConfig,
                 anneal_cfg: Optional[AnnealConfig] = None, mesh=None,
                 backend=None):
        super().__init__(train_cfg, mesh=mesh, backend=backend)
        self.model_cfg = model_cfg
        self.anneal_cfg = anneal_cfg or AnnealConfig()

        # graftmend (train/actions.py): temperature-schedule rebase point —
        # reanneal_gumbel(step) restarts the anneal from `step`, re-warming
        # a collapsed codebook; temp is a traced scalar so no recompile
        self._anneal_step0 = 0

        with span("init/model"):
            self.model, params = init_dvae(model_cfg, self.base_key)
        self.state = self._create_state(params, self.model.apply)
        self._health_kw = dict(
            health=bool(train_cfg.obs.health),
            health_depth=train_cfg.obs.health_group_depth)
        with span("init/build_step"):
            self.step_fn = make_vae_train_step(
                self.model, dtype=compute_dtype(train_cfg.precision),
                state=self.state, **self._health_kw)
        self._multi_step_fn = None   # built lazily on first train_steps()

        n = count_params(self.state.params)
        self.meter = ThroughputMeter(train_cfg.batch_size, train_cfg.log_every,
                                     flops_per_step=6.0 * n * train_cfg.batch_size *
                                     model_cfg.image_seq_len,
                                     num_chips=self.mesh.size)

    def _put_batch(self, batch, stacked: bool = False):
        """(images[, labels]) → float32 images on the mesh; trailing labels
        (ignored by the step) pass through as-is."""
        images, *rest = batch
        return (self._put(images, np.float32, stacked), *rest)

    def _temp_at(self, step: int) -> float:
        """Anneal temperature with the re-anneal rebase applied: the
        schedule runs on ``step - _anneal_step0`` so a codebook-collapse
        action can restart the warm phase mid-run (docs/RESILIENCE.md)."""
        return anneal_temperature(self.anneal_cfg,
                                  max(step - self._anneal_step0, 0))

    def reanneal_gumbel(self, step: int) -> float:
        """Restart the gumbel temperature schedule from ``step`` (the
        codebook-collapse breach action). Returns the re-warmed temp.
        The rebase point rides checkpoint METADATA (``extra_meta`` flows
        into every later save's sidecar) so a preemption/respawn resumes
        the re-warmed schedule instead of snapping back to the cold
        end-of-schedule temperature — the lr-cut action gets the same
        durability from ``TrainState.lr_scale`` living in the state."""
        self._anneal_step0 = int(step)
        self.extra_meta["anneal_step0"] = self._anneal_step0
        return self._temp_at(step)

    def restore(self, step=None):
        meta = super().restore(step)
        if meta and meta.get("anneal_step0"):
            # best-effort like all metadata: a missing sidecar resumes the
            # un-rebased schedule (and a breach would just re-fire)
            self._anneal_step0 = int(meta["anneal_step0"])
            self.extra_meta["anneal_step0"] = self._anneal_step0
        return meta

    # -- single step -------------------------------------------------------
    def train_step(self, images: np.ndarray, _labels=None):
        step_num = self._host_step
        temp = self._temp_at(step_num)
        key = jax.random.fold_in(self.base_key, step_num)
        with span("vae/shard_batch"):
            images = self._put(images, np.float32)
        with span("vae/step"):
            self.state, metrics = self._run_step(
                self.step_fn, self.state, images, key, jnp.float32(temp))
        # the stamp travels with this step's record: under fit() the record
        # handed back is the previous boundary's
        return self._finish_step(metrics, {"temperature": temp})

    # -- k steps in one device program ---------------------------------------
    def train_steps(self, images: np.ndarray, _labels=None):
        """(k, b, H, W, C) stacked microbatches → k optimizer steps in one
        dispatched scan. Key and temperature streams match ``train_step``
        exactly (precomputed per host step and scanned as inputs), so the
        result is identical to k single dispatches. ``_labels`` (stacked
        captions from the (images, captions) loaders) is ignored, mirroring
        ``train_step``."""
        assert images.ndim == 5, "train_steps wants stacked (k, b, H, W, C)"
        if self._multi_step_fn is None:
            self._multi_step_fn = make_vae_train_multi_step(
                self.model, dtype=compute_dtype(self.train_cfg.precision),
                **self._health_kw)
        k = images.shape[0]
        steps = self._host_step + np.arange(k)
        keys = self._step_keys(k)
        temps = [self._temp_at(int(s)) for s in steps]
        with span("vae/shard_batch", k=k):
            images = self._put(images, np.float32, stacked=True)
        with span("vae/steps", k=k):
            self.state, metrics = self._run_step(
                self._multi_step_fn, self.state,
                (images, keys, jnp.asarray(temps, jnp.float32)))
        self._host_step += k - 1     # _finish_step adds the final +1
        return self._finish_step(metrics, {"temperature": float(temps[-1])})

    # -- eval utilities ----------------------------------------------------
    def reconstruct(self, images: np.ndarray, hard: bool = True):
        return self.model.apply(self.state.params, jnp.asarray(images),
                                hard_recons=hard,
                                rngs=None if hard else {"gumbel": self.base_key})

    def codebook_histogram(self, images: np.ndarray) -> np.ndarray:
        idx = self.model.apply(self.state.params, jnp.asarray(images),
                               method=DiscreteVAE.get_codebook_indices)
        return np.asarray(_codebook_counts(idx, self.model_cfg.num_tokens))
